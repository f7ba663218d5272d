"""Host seconds per round in the scheduler's ``aggregate`` phase node: the
server's masked-mean teacher. Read from ``RoundLog.phase_s`` and
averaged over the measured window's rounds."""

PHASE = "aggregate"


def read(ctx):
    vals = [r["phase_s"][PHASE] for r in ctx.rounds if PHASE in r["phase_s"]]
    return sum(vals) / len(vals) if vals else None
