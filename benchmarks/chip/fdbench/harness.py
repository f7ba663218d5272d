"""One run of one cell: set-up, the measured window, the traced window, and
the comparison with the reference.

Everything about a cell is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic; ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json`` hold them,
``models/<model>.py`` holds what depends on the configuration's kind of
client model (``fdbench.kinds``), and ``metrics/<metric>.py`` reads each
per-layer metric.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from fdbench import kinds
from fdbench.kinds import Refused

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
# JAX records this around every program it builds, also when it reads the
# program from the persistent cache; a read records CACHE_HIT_EVENT as well
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_MIN_ROUNDS = 3
TRACE_MIN_SECONDS = 2.0
FOLLOWED_ROUNDS = 3
# traffic keys the harness reads itself; every other traffic key is a
# field of the program's FedConfig
HARNESS_KEYS = ("samples_per_client", "n_test", "source", "assumed")


class CompileCounter:
    """Counts the programs JAX builds (``count``) and how many of them it
    read from the persistent cache (``hits``); one listener per process.
    ``count - hits`` programs were compiled."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        self.count = 0
        self.hits = 0
        self.seconds = 0.0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax
            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._listen)
            jax.monitoring.register_event_listener(cls._instance._hit)
        return cls._instance

    def _listen(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _hit(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def since(self, mark) -> str:
        """``mark`` is ``(count, hits)`` as they were; what was built
        since, in words."""
        n, h = self.count - mark[0], self.hits - mark[1]
        return f"{n} programs built, {n - h} compiled, {h} from the cache"

    def mark(self):
        return self.count, self.hits


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str) -> SimpleNamespace:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    kinds.load(config)
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(HERE / "limits" / f"{name}.json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return SimpleNamespace(
        name=name, cell=cell, config=config, traffic=traffic, limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def load_reader(metric: str) -> Callable:
    return kinds.load_file(HERE / "metrics" / f"{metric}.py",
                           "fdbench_metric_" + metric.replace(".", "_")).read


# --------------------------------------------------------------- the program
def fed_config(traffic: Dict, seed: int):
    """The program's ``FedConfig``: every traffic key that names one of its
    fields, and the run's seed. A key that is neither a field nor one of
    ``HARNESS_KEYS`` is refused, so that a misspelt knob cannot measure
    the default."""
    from repro.common.types import FedConfig
    fields = {f.name for f in dataclasses.fields(FedConfig)} - {"seed"}
    unknown = sorted(set(traffic) - fields - set(HARNESS_KEYS))
    if unknown:
        raise Refused(f"traffic keys {unknown} are neither FedConfig fields "
                      f"nor the harness's {list(HARNESS_KEYS)}")
    return FedConfig(**{k: v for k, v in traffic.items() if k in fields},
                     seed=seed)


def build(config: Dict, traffic: Dict, data, seed: int) -> SimpleNamespace:
    """The program's own set-up, fed the benchmark's data through the
    simulator's ``make_dataset``."""
    import jax

    import repro.fed.simulator as simulator
    from repro.core.methods import get_method
    from repro.core.protocol import engine_from_config
    from repro.fed.scheduler import RoundScheduler

    if not hasattr(simulator, "make_dataset"):
        raise Refused("repro.fed.simulator.make_dataset is gone: the "
                      "benchmark can no longer feed the simulator its data")
    cfg = fed_config(traffic, seed)
    own = simulator.make_dataset
    simulator.make_dataset = lambda *a, **k: data
    try:
        clients, server, x_test, y_test = simulator.build_experiment(
            cfg, config["dataset"]["name"], n_train=len(data.y),
            n_test=len(data.y_test), **kinds.load(config).build_kwargs(config))
    finally:
        simulator.make_dataset = own
    engine = engine_from_config(clients, cfg)
    t0 = time.perf_counter()
    engine.learn_dres(jax.random.PRNGKey(seed))
    block(engine)
    t_dre = time.perf_counter() - t0
    sched = RoundScheduler(engine, server, get_method(cfg.method), cfg,
                           x_test, y_test)
    return SimpleNamespace(engine=engine, sched=sched, server=server,
                           clients=clients, dre_s=t_dre)


def block(engine) -> None:
    import jax
    for c in engine.cohorts:
        jax.block_until_ready((c.params, c.opt_state))


def member_leaves(engine, tree_of: Callable) -> Iterator[Tuple[int, List]]:
    """Each client id with its leaves sliced out of the cohorts' stacked
    trees, in ``jax.tree_util``'s order as the reference takes them (for a
    list of per-layer dicts: layer by layer, keys sorted). One client at a
    time: a slice of a cohort split over chips lands on every chip."""
    import jax
    for c in engine.cohorts:
        leaves = jax.tree_util.tree_leaves(tree_of(c))
        for j, cid in enumerate(c.positions):
            yield cid, [leaf[j] for leaf in leaves]


def leaf_norms(engine, tree_of: Callable,
               minus: Optional[Dict[int, List]] = None) -> List[float]:
    """The norm of every leaf of every client, by client id; of each leaf
    less its leaf in ``minus[cid]`` where given. Each client is read
    before the next is sliced."""
    import jax
    import jax.numpy as jnp
    norms = {}
    for cid, leaves in member_leaves(engine, tree_of):
        if minus is not None:
            leaves = [a - jax.device_put(b, a.sharding)
                      for a, b in zip(leaves, minus[cid])]
        norms[cid] = jax.device_get(
            [jnp.linalg.norm(jnp.ravel(a).astype(jnp.float32))
             for a in leaves])
    return [float(v) for cid in sorted(norms) for v in norms[cid]]


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def check_shapes(engine, config: Dict) -> None:
    """Every client's parameters have the shapes, at the paths, that its
    model kind declares."""
    from jax.tree_util import keystr, tree_flatten_with_path

    from fdbench import flops
    for c in engine.cohorts:
        got = [(keystr(p), tuple(v.shape[1:]))
               for p, v in tree_flatten_with_path(c.params)[0]]
        for cid in c.positions:
            want = [(keystr(p), tuple(s)) for p, s in tree_flatten_with_path(
                flops.param_shapes(config, cid), is_leaf=_is_shape)[0]]
            if got != want:
                raise Refused(f"client {cid}'s parameters {got} differ from "
                              f"the configuration's declared {want}")


def check_placement(engine, chips: int) -> None:
    """The cohorts' parameters span at least the cell's ``chips`` devices,
    so that a cell on four chips cannot measure one."""
    import jax
    spanned = {d for c in engine.cohorts
               for leaf in jax.tree_util.tree_leaves(c.params)
               for d in leaf.devices()}
    if len(spanned) < chips:
        raise Refused(f"the cell asks for {chips} chips, the cohorts' "
                      f"parameters span {len(spanned)} device(s)")


def run_round(sched, r: int, on_step: Optional[Callable] = None):
    sched.begin(r, 1)
    log = None
    while sched.has_pending():
        if on_step is None:
            _, _, got = sched.step()
        else:
            got = on_step(sched)
        log = got or log
    return log


def finite(log) -> bool:
    vals = [log.local_loss, log.distill_loss, *log.accs]
    return all(math.isfinite(v) for v in vals)


def follow(prog) -> Dict:
    """Drive the first rounds through the scheduler and read what the
    comparison needs (``fdbench.compare``): each round's losses, the
    clients' accuracies and ID fractions, the per-leaf momentum norms
    after round 0 and the per-leaf change of the parameters over the
    rounds."""
    import jax
    engine, sched = prog.engine, prog.sched
    # the starting point, each client's on one of the chips its cohort spans
    p0 = {}
    for cid, leaves in member_leaves(engine, lambda c: c.params):
        chips = sorted(leaves[0].devices(), key=lambda d: d.id)
        p0[cid] = jax.device_put(leaves, chips[cid % len(chips)])
    readings = {"local_loss": [], "distill_loss": [], "accs": [],
                "id_fracs": []}
    for r in range(FOLLOWED_ROUNDS):
        lg = run_round(sched, r)
        readings["local_loss"].append(lg.local_loss)
        readings["distill_loss"].append(lg.distill_loss)
        readings["accs"].append(list(lg.accs))
        readings["id_fracs"].append(list(lg.client_id_fractions))
        if r == 0:
            readings["grad_norms"] = leaf_norms(
                engine, lambda c: c.opt_state["mu"])
    readings["change_norms"] = leaf_norms(engine, lambda c: c.params, p0)
    readings["leaf_shapes"] = [list(a.shape) for cid in sorted(p0)
                               for a in p0[cid]]
    block(engine)
    return readings


def make_data(config: Dict, traffic: Dict, seed: int):
    """The cell's data from the seed, made by the model kind's
    ``make_dataset`` where it has one."""
    from fdbench import fleetdata
    make = getattr(kinds.load(config), "make_dataset",
                   fleetdata.make_dataset)
    return make(config["dataset"], traffic["num_clients"]
                * traffic["samples_per_client"], traffic["n_test"], seed)


def set_up(config: Dict, traffic: Dict, seed: int, split: Dict,
           chips: int):
    """Data, the program's build and DRE fit; returns (data, program)."""
    t0 = time.perf_counter()
    data = make_data(config, traffic, seed)
    split["data_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    prog = build(config, traffic, data, seed)
    check_shapes(prog.engine, config)
    check_placement(prog.engine, chips)
    split["build_s"] = time.perf_counter() - t0 - prog.dre_s
    split["dre_s"] = prog.dre_s
    return data, prog


def enable_cache() -> None:
    """The program's compile cache, with every program kept (JAX keeps
    only those that took over a second to compile by default)."""
    import jax

    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------------------------------------------ the run
def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path, t_process: float, require_tpu: bool = True,
        traffic_overrides: Optional[Dict] = None,
        readings_source: Optional[Callable] = None,
        log: Callable = print) -> Dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``traffic_overrides`` and ``readings_source`` serve the tests: the
    first shrinks a cell to a CPU-sized fleet; the second replaces the
    program's readings (the control puts the lower-precision reference in
    the program's place).
    """
    import jax

    from fdbench import compare, flops, reference, xplane

    cell = load_cell(root, name)
    peaks_table = load_json(HERE / "peaks.json")["devices"]
    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise Refused(f"no TPU: JAX found {dev.platform!r}")
        if len(devices) < cell.cell["chips"]:
            raise Refused(f"cell asks for {cell.cell['chips']} chips, "
                          f"JAX found {len(devices)}")
        if dev.device_kind not in peaks_table:
            raise Refused(f"device kind {dev.device_kind!r} is not in "
                          "peaks.json")
    peaks = peaks_table.get(dev.device_kind)
    counter = CompileCounter.get()
    c_start = counter.mark()
    enable_cache()
    traffic = dict(cell.traffic, **(traffic_overrides or {}))
    config = cell.config
    split = {}
    data, prog = set_up(config, traffic, seed, split, cell.cell["chips"])

    # the followed rounds: the same engine and scheduler the window drives
    t0 = time.perf_counter()
    engine, sched = prog.engine, prog.sched
    readings = follow(prog)
    split["warm_rounds_s"] = time.perf_counter() - t0
    compiles_setup = counter.since(c_start)
    setup_s = time.perf_counter() - t_process

    # the measured window
    c0 = counter.mark()
    walls, logs = [], []
    r = FOLLOWED_ROUNDS
    t_w0 = time.perf_counter()
    t_last = t_w0
    while t_last - t_w0 < seconds:
        t_r = time.perf_counter()
        logs.append(run_round(sched, r))
        block(engine)
        t_last = time.perf_counter()
        walls.append(t_last - t_r)
        r += 1
    window_s = t_last - t_w0
    compiles_window = counter.since(c0)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)

    rounds = [{"wall_s": w, "phase_s": dict(lg.phase_s)}
              for w, lg in zip(walls, logs)]
    round_s = window_s / len(walls)
    ctx = SimpleNamespace(
        rounds=rounds, round_s=round_s, peaks=peaks, config=config,
        traffic=traffic, trace=None, phases=None, chips=cell.cell["chips"],
        round_flops=flops.round_flops(
            config, traffic, [len(c.members) for c in engine.cohorts],
            1 if traffic["scenario"] == "strong" else config["num_classes"]))

    breakdown = None
    dev_info = {"platform": dev.platform, "kind": dev.device_kind,
                "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    if trace:
        ctx.trace, ctx.phases = traced_window(sched, engine, r, name, root,
                                              log)
        dev_info["busy_s"] = ctx.trace.busy_s
        dev_info["window_s"] = ctx.trace.window_s
        breakdown = xplane.breakdown(ctx.trace, ctx.phases)
        gaps = xplane.gap_seconds_by_phase(ctx.trace, ctx.phases)
        log(f"idle seconds by host phase in the traced window: {gaps}",
            file=sys.stderr)

    phase_means = {}
    for rd in rounds:
        for k, v in rd["phase_s"].items():
            phase_means[k] = phase_means.get(k, 0.0) + v / len(rounds)
    log(f"set-up split: {json.dumps(split)} total setup_s {setup_s!r} "
        f"({compiles_setup})", file=sys.stderr)
    log(f"window: {len(walls)} rounds in {window_s!r} s; in the window: "
        f"{compiles_window}", file=sys.stderr)
    log(f"mean phase seconds per round: {json.dumps(phase_means)}",
        file=sys.stderr)

    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s, "round_s": round_s,
               "round_p95_s": (statistics.quantiles(walls, n=20)[-1]
                               if len(walls) >= 2 else walls[0]),
               "peak_hbm_gib": peak_bytes / 2 ** 30}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    failed = sum(not finite(lg) for lg in logs)

    # the reference, once the program's state is freed
    del engine, sched, prog, logs
    gc.collect()
    t0 = time.perf_counter()
    ref = reference.run(config, traffic, data, seed,
                        devices=devices[:cell.cell["chips"]],
                        rounds=FOLLOWED_ROUNDS)
    if readings_source is not None:
        readings = readings_source(config, traffic, data, seed)
    nums = compare.numbers(readings, ref)
    ok, checks, lines = compare.verdict(nums, cell.limits)
    log(f"reference: {time.perf_counter() - t0!r} s", file=sys.stderr)
    for line in lines:
        log(line, file=sys.stderr)
    result = {"correct": bool(ok), "attempted": len(walls), "failed": failed,
              "metrics": metrics, "device": dev_info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def traced_window(sched, engine, r0: int, name: str, root: Path,
                  log: Callable):
    """Trace a steady window of whole rounds (at least three and two
    seconds); return the reduced trace and the phase of every step."""
    import jax

    from fdbench import xplane

    out = root / "benchmarks" / "results" / "trace" / name
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    phases: List[str] = []

    def on_step(s):
        with jax.profiler.TraceAnnotation(xplane.STEP_SPAN):
            phase, _, got = s.step()
        phases.append(phase)
        return got

    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        t0 = time.perf_counter()
        n = 0
        while n < TRACE_MIN_ROUNDS or time.perf_counter() - t0 \
                < TRACE_MIN_SECONDS:
            with jax.profiler.TraceAnnotation(xplane.ROUND_SPAN):
                run_round(sched, r0 + n, on_step)
                block(engine)
            n += 1
    finally:
        jax.profiler.stop_trace()
    files = sorted(out.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise Refused("the profiler wrote no trace")
    log(f"traced {n} rounds into {files[-1]}", file=sys.stderr)
    return xplane.Trace.from_file(str(files[-1])), phases
