"""Share of the traced steady window (whole rounds) in which no operation
ran on the device, in percent: 100 x (1 - union of XLA op intervals /
window)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
