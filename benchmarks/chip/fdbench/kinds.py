"""Everything that depends on the kind of client model a configuration
names, found by that name: ``models/<config["model"]>.py``.

A model module provides

* ``param_shapes(config, cid)``: client ``cid``'s parameter shapes, a
  pytree of tuples in the program's layout;
* ``forward_flops(config, cid)``: the FLOPs of one sample's forward pass;
* ``filter_dim(config)``: the width of the rows the KMeans-DRE filter reads;
* ``init_params(key, config, cid)`` and ``make_apply(config, cid,
  precision)``: the plain reference model, ``apply(params, x, train)``
  giving (n, K) logits;
* ``arch_key(config, cid)``: equal for clients that share one reference
  model, so they share its compiled functions;
* ``build_kwargs(config)``: keyword arguments for the program's
  ``repro.fed.simulator.build_experiment``;

and may provide ``make_dataset(spec, n_train, n_test, seed)``, which
otherwise is ``fdbench.fleetdata.make_dataset``.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
REQUIRED = ("param_shapes", "forward_flops", "filter_dim", "init_params",
            "make_apply", "arch_key", "build_kwargs")


class Refused(RuntimeError):
    """The run cannot give a result (no chip, unknown device, bad cell)."""


def load_file(path: Path, name: str) -> ModuleType:
    """The module in ``path``, executed once per process under ``name``."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def load(config: dict) -> ModuleType:
    """The module of the configuration's model kind."""
    kind = config["model"]
    path = HERE / "models" / f"{kind}.py"
    if not path.is_file():
        raise Refused(f"model kind {kind!r} has no module: "
                      f"{path.relative_to(HERE.parents[1])} is missing")
    mod = load_file(path, f"fdbench_model_{kind}")
    missing = [f for f in REQUIRED if not hasattr(mod, f)]
    if missing:
        raise Refused(f"{path.relative_to(HERE.parents[1])} lacks "
                      f"{', '.join(missing)}")
    return mod
