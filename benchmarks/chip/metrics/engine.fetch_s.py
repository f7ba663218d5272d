"""Host seconds per round in the cohort engine's ``cohort.fetch`` spans
(each device->host read: the wait for the device, then the copy), summed
over the traced window and divided by its rounds. Returns nothing where
the program opens no such spans."""
from fdbench import spans


def read(ctx):
    got = spans.of(ctx)
    return None if got is None else got.per_round_s("cohort.fetch")
