"""Scheduler self time per round: the round's wall time (host clock, from
the first ``begin`` to the device's last result) less the time its phase
nodes took (``RoundLog.phase_s``), averaged over the measured window."""


def read(ctx):
    if not ctx.rounds:
        return None
    return sum(r["wall_s"] - sum(r["phase_s"].values())
               for r in ctx.rounds) / len(ctx.rounds)
