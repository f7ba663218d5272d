"""Device->host reads of the cohort engine per round: the ``cohort.fetch``
spans in the traced window over its rounds. The program opens one such
span for each read it books as ``RoundLog.counters["engine.syncs"]``.
Returns nothing where the program opens no such spans."""
from fdbench import spans


def read(ctx):
    got = spans.of(ctx)
    return None if got is None else got.per_round_count("cohort.fetch")
