"""Model FLOP utilization of the whole round, in percent: the model FLOPs
one round of the protocol requires on all the cell's chips (counted from
the configuration's declared layers, ``fdbench.flops.round_flops``) over
the measured ``round_s`` times the cell's chips times one chip's peak bf16
FLOP/s (``peaks.json``)."""


def read(ctx):
    if ctx.peaks is None or not ctx.round_s:
        return None
    return 100.0 * ctx.round_flops / (ctx.round_s * ctx.chips
                                      * ctx.peaks["bf16_flops"])
