"""MaxText-style logical-axis sharding annotations.

Model code annotates activations with *logical* axis names
(``constrain(x, "batch", None, "model_ff")``). The launcher installs a
logical→mesh-axis mapping (``set_logical_rules``) before tracing; outside a
mesh context the annotation is a no-op, so the same model code runs on a
single CPU device in tests and fully sharded in the dry-run.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()

# logical axis name -> mesh axis name (or tuple of mesh axes, or None)
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "kv_seq": None,
    "vision_seq": None,
}

# beyond-paper sharding profiles (EXPERIMENTS.md §Perf):
#   2d  — baseline: batch over (pod,data), tensor-parallel over model, FSDP
#         params over data. General-purpose, collective-heavy for small models.
#   dp  — pure data parallel: batch over EVERY axis, params replicated.
#         Kills all TP activation collectives; only grad all-reduce remains.
#         Small models only (params must fit one device).
#   tp  — tensor parallel without FSDP: params sharded over model only,
#         batch over (pod,data). No per-step param gathers — decode's friend.
PROFILES = {
    "2d": DEFAULT_RULES,
    "dp": {**{k: None for k in DEFAULT_RULES},
           "batch": ("pod", "data", "model")},
    "tp": DEFAULT_RULES,
}


def set_logical_rules(rules: Optional[dict], mesh=None) -> None:
    _state.rules = rules
    _state.mesh = mesh


@contextlib.contextmanager
def logical_rules(rules: Optional[dict], mesh=None):
    """Scoped ``set_logical_rules``: installs (rules, mesh) for the duration
    of the block and restores the previous mapping on exit. Engines that own
    a private mesh (e.g. the cohort engine's 1-D client mesh) wrap their
    jitted-call sites in this so traces triggered inside pick up the right
    rules without leaking them into unrelated code."""
    prev = (getattr(_state, "rules", None), getattr(_state, "mesh", None))
    set_logical_rules(rules, mesh)
    try:
        yield
    finally:
        set_logical_rules(*prev)


def get_mesh():
    return getattr(_state, "mesh", None)


def _resolve(axis: Optional[str], rules: dict, mesh_axes) -> Optional[Union[str, tuple]]:
    if axis is None:
        return None
    m = rules.get(axis, None)
    if m is None:
        return None
    if isinstance(m, tuple):
        kept = tuple(a for a in m if a in mesh_axes)
        return kept if kept else None
    return m if m in mesh_axes else None


def logical_spec(*axes: Optional[str]) -> Optional[P]:
    rules = getattr(_state, "rules", None)
    mesh = getattr(_state, "mesh", None)
    if rules is None or mesh is None:
        return None
    mesh_axes = set(mesh.axis_names)
    return P(*[_resolve(a, rules, mesh_axes) for a in axes])


def constrain(x, *axes: Optional[str]):
    """Apply a sharding constraint if a mesh/rule set is installed."""
    spec = logical_spec(*axes)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(getattr(_state, "mesh"), spec))


def shard_local(fn, in_axes, out_axes):
    """``fn`` run on each device's local blocks of the installed mesh.

    A Pallas TPU kernel has no partitioning rule, so under a mesh XLA
    refuses to split it; its call sites wrap it here. ``in_axes`` gives one
    tuple of logical axis names per argument (one name or None per dim,
    resolved like ``constrain``); ``out_axes`` is one such tuple, or a list
    of them for a tuple of results. Inside a ``vmap`` over a mesh-sharded
    axis, pass the vmap ``spmd_axis_name`` so that its batch dim joins the
    specs. Without an installed mesh, ``fn`` itself is returned.
    """
    rules = getattr(_state, "rules", None)
    mesh = getattr(_state, "mesh", None)
    if rules is None or mesh is None:
        return fn
    names = set(mesh.axis_names)

    def spec(axes):
        return P(*[_resolve(a, rules, names) for a in axes])

    out_specs = (tuple(spec(a) for a in out_axes)
                 if isinstance(out_axes, list) else spec(out_axes))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(a) for a in in_axes),
                         out_specs=out_specs, check_vma=False)


def gather_fsdp(params_subtree):
    """Explicit ZeRO-3 weight gathering (EXPERIMENTS.md §Perf pair A).

    Called INSIDE the traced layer body: constrains every weight leaf to its
    name-aware spec with the 'data' (FSDP) axis removed. GSPMD then
    materialises one weight all-gather per use (537 MB for llama-405B wq)
    instead of re-sharding the residual activations (4.3 GB f32, measured) —
    the cost model picks the activation path without this hint. No-op when
    no mesh is installed or FSDP is off (specs match).
    """
    mesh = getattr(_state, "mesh", None)
    if mesh is None:
        return params_subtree
    from repro.launch.mesh import param_spec  # local import: no cycle at load

    def leaf(path, w):
        name = next((str(p.key) for p in reversed(path)
                     if hasattr(p, "key")), None)
        spec = param_spec(w.shape, mesh, n_stack_axes=0, fsdp=False,
                          name=name)
        return jax.lax.with_sharding_constraint(
            w, jax.sharding.NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(leaf, params_subtree)
