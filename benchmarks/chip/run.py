"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json`` and the
program under ``src/``. The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each compared
number beside its limit). Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits 1.
"""
import os
import sys
import time


def _process_age() -> float:
    """Seconds since this process started (set-up counts from there)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))


T_PROCESS = time.perf_counter() - _process_age()


def main(argv=None) -> int:
    import argparse
    import json
    from pathlib import Path

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = Path(__file__).resolve().parents[2]
    if not (root / "BENCHMARK.json").is_file() or not (root / "src").is_dir():
        print(f"{root} holds no BENCHMARK.json and program to run",
              file=sys.stderr)
        return 1
    # JAX's persistent compile cache lives in the checkout, at a fixed path
    # (the path is part of every entry's key), and nowhere else
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"   # no logs under /tmp
    sys.path.insert(0, str(root / "src"))
    from fdbench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), root=root,
                             t_process=T_PROCESS)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
