"""Host seconds per round in the scheduler's ``local_train`` phase node:
client training (the cohorts' vmapped SGD scans). Read from
``RoundLog.phase_s`` and averaged over the measured window's rounds."""

PHASE = "local_train"


def read(ctx):
    vals = [r["phase_s"][PHASE] for r in ctx.rounds if PHASE in r["phase_s"]]
    return sum(vals) / len(vals) if vals else None
