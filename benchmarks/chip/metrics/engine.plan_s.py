"""Host seconds per round in the cohort engine's ``cohort.plan`` spans
(every client's epoch permutations, packed into the step plans), summed
over the traced window and divided by its rounds. Returns nothing where
the program opens no such spans."""
from fdbench import spans


def read(ctx):
    got = spans.of(ctx)
    return None if got is None else got.per_round_s("cohort.plan")
