"""Host seconds per round in the scheduler's ``eval`` phase node: every
client's test-set evaluation. Read from ``RoundLog.phase_s`` and
averaged over the measured window's rounds."""

PHASE = "eval"


def read(ctx):
    vals = [r["phase_s"][PHASE] for r in ctx.rounds if PHASE in r["phase_s"]]
    return sum(vals) / len(vals) if vals else None
