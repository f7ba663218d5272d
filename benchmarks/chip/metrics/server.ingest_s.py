"""Host seconds per round in the server's ``server.ingest`` spans (scrub,
fault injection, admission, staleness merge, ``Server.ingest_reports``),
summed over the traced window and divided by its rounds. Returns nothing
where the program opens no such spans."""
from fdbench import spans


def read(ctx):
    got = spans.of(ctx)
    return None if got is None else got.per_round_s("server.ingest")
