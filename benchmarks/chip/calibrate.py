"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --program-seeds 1,2,... --control-seeds 7,8,9 --out readings.json

For every program seed: the cell's own set-up and followed rounds (the
timed path's entry and compiled programs, at the cell's sizes) against the
float32 reference; these give each number's lower reading. For every
control seed: the reference in bfloat16 at default precision (the control)
and the reference with each planted fault, all in the program's place,
against the float32 reference; these give the upper readings. Each line
also says whether ``compare.verdict`` under the cell's limits file calls
it correct. Run it on the chip at the cell's own sizes; the benchmark's
own runs never run it.
"""
import os
import sys
import time


def main(argv=None) -> int:
    import argparse
    import json
    from pathlib import Path

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"   # no logs under /tmp
    sys.path.insert(0, str(root / "src"))
    import gc

    import jax
    import jax.numpy as jnp

    from fdbench import compare, harness, reference

    cell = harness.load_cell(root, args.workload)
    config = cell.config
    traffic = cell.traffic
    devices = jax.devices()[:cell.cell["chips"]]
    harness.enable_cache()
    counter = harness.CompileCounter.get()
    out = {"workload": args.workload, "program": {}, "control": {},
           "faults": {}}

    def dump():
        with open(args.out, "w") as f:
            json.dump(out, f)

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    def note(kind, seed, nums, extra=""):
        ok = compare.verdict(nums, cell.limits)[0]
        print(f"{kind} seed {seed}: " + " ".join(
            f"{k}={v!r}" for k, v in nums.items())
            + f" correct={ok}" + extra, flush=True)

    for seed in seeds(args.program_seeds):
        t0 = time.perf_counter()
        c0 = counter.mark()
        split = {}
        data, prog = harness.set_up(config, traffic, seed, split,
                                    cell.cell["chips"])
        readings = harness.follow(prog)
        compiles = counter.since(c0)
        del prog
        gc.collect()
        t1 = time.perf_counter()
        ref = reference.run(config, traffic, data, seed, devices=devices)
        nums = compare.numbers(readings, ref)
        out["program"][seed] = {"numbers": nums, "program": readings,
                                "reference": ref}
        note("program", seed, nums, f" (set-up {t1 - t0:.1f} s: "
             f"{compiles}; reference "
             f"{time.perf_counter() - t1:.1f} s)")
        for key in ("local_loss", "distill_loss"):
            print(f"  {key}: program {readings[key]} reference {ref[key]}",
                  flush=True)
        dump()
    for seed in seeds(args.control_seeds):
        data = harness.make_data(config, traffic, seed)
        t0 = time.perf_counter()
        ref = reference.run(config, traffic, data, seed, devices=devices)
        t1 = time.perf_counter()
        ctrl = reference.run(config, traffic, data, seed, devices=devices,
                             dtype=jnp.bfloat16, precision=None)
        nums = compare.numbers(ctrl, ref)
        out["control"][seed] = {"numbers": nums}
        note("control", seed, nums, f" (reference {t1 - t0:.1f} s)")
        for fault in reference.FAULTS:
            bad = reference.run(config, traffic, data, seed,
                                devices=devices, fault=fault)
            nums = compare.numbers(bad, ref)
            out["faults"].setdefault(fault, {})[seed] = {"numbers": nums}
            note(f"fault {fault}", seed, nums)
        dump()
    for kind in ("program", "control"):
        vals = [v["numbers"] for v in out[kind].values()]
        if vals:
            agg = max if kind == "program" else min
            print(f"{kind} {'largest' if kind == 'program' else 'smallest'}: "
                  + " ".join(f"{k}={agg(v[k] for v in vals)!r}"
                             for k in compare.NUMBERS), flush=True)
    dump()
    return 0


if __name__ == "__main__":
    sys.exit(main())
