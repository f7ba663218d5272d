"""Share of its roofline that the distill-KL kernels reach, in percent.

Device time: every forward and backward kernel call in the traced window,
found by the HLO instruction names the kernels carry today (the custom
calls that ``jit(_run)`` and ``jit(_run_bwd)`` of
``repro.kernels.distill_kl.ops`` lower to). Roofline: for each call, the
larger of its FLOPs over peak FLOP/s and its bytes over peak bandwidth,
counted for the rows the protocol needs: the call's clients (the leading
dimension of its result) times the distill batch, K logits each. The
kernels' padding to their block does not count. Returns nothing where the
trace holds no such call.
"""
import sys

from fdbench import flops, xplane

KINDS = (("fwd", r"^(jvp_)?jit__run__"),
         ("bwd_ds", r"jit__run_bwd_"))


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    k = ctx.config["num_classes"]
    batch = min(ctx.traffic["batch_size"], ctx.traffic["proxy_batch"])
    need = spent = 0.0
    bounds = set()
    for kind, pattern in KINDS:
        for name, seconds in ctx.trace.kernel_calls(pattern):
            dims = xplane.result_dims(name)
            clients = dims[0] if len(dims) == 3 else 1
            t_min, bound = flops.roofline_seconds(
                *flops.distill_kl_cost(clients * batch, k, kind), ctx.peaks)
            need += t_min
            spent += seconds
            bounds.add(bound)
    if spent <= 0.0:
        return None
    print(f"distill_kl_roofline: bound by {sorted(bounds)}; "
          f"{need!r} s needed in {spent!r} s of kernel time",
          file=sys.stderr)
    return 100.0 * need / spent
