"""Scheduler self time per round, from the program's spans: the
``sched.step`` spans (selection, the node, pricing, retirement) less their
child spans (``phase.<name>``, ``server.ingest``), summed over the traced
window and divided by its rounds. Returns nothing where the program opens
no such spans."""
from fdbench import spans


def read(ctx):
    got = spans.of(ctx)
    return None if got is None else got.own_s("sched.step")
