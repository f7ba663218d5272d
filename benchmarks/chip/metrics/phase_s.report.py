"""Host seconds per round in the scheduler's ``report`` phase node: proxy
logits and KMeans-DRE masks on the engine, then the server's report
ingest. Read from ``RoundLog.phase_s`` and averaged over the measured
window's rounds."""

PHASE = "report"


def read(ctx):
    vals = [r["phase_s"][PHASE] for r in ctx.rounds if PHASE in r["phase_s"]]
    return sum(vals) / len(vals) if vals else None
