"""Spans and counters of the federated round.

Spans are ``jax.profiler.TraceAnnotation``s: with no profiler session they
cost one check; under one they land on the host plane of the trace, on the
clock of the device ops. A span carries the metadata of the node it runs
in (``round=r``, and ``cohort=ci`` on per-cohort nodes), so the spans of
one round share an identifier also under ``round_mode="overlap"``.

=====================  ==================================================
``sched.step``         one ``RoundScheduler.step``: selection, the node,
                       pricing, retirement
``phase.<name>``       a phase node's body
``server.ingest``      the server's report ingest, after the report node
``server.aggregate``   the server's reduce, byte accounting, outliers
``server.fetch``       a device->host read in the server
``cohort.plan``        the cohort's batch plans, packed by client size
``cohort.stage``       host->device staging
``cohort.launch``      one call of a jitted cohort program
``cohort.fetch``       a device->host read in the cohort engine
=====================  ==================================================

Only ``span`` (``phase.<name>``, ``server.ingest``) reads the clock with
the profiler off: ``RoundLog.phase_s`` is its duration.

Counters are integer adds, always on: ``engine.syncs`` (one per
``cohort.fetch``), ``server.syncs`` (one per ``server.fetch``),
``engine.plan_groups`` (one per client-size group ``cohort.plan``
packs) and ``compiles`` (programs JAX built, from the compile event
the benchmark counts too; ``compiles()`` also sums their seconds). They
are process totals, counted from the first call of ``counts`` or
``compiles``; the scheduler books each node's increments on its round
(``RoundLog.counters``) with ``counts`` and ``book``.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COUNTERS = ("engine.syncs", "server.syncs", "engine.plan_groups",
            "compiles")
_FETCH = {"engine": ("cohort.fetch", "engine.syncs"),
          "server": ("server.fetch", "server.syncs")}

_totals: Dict[str, int] = dict.fromkeys(COUNTERS, 0)
_compile_s = [0.0]
_listening = [False]
_meta: Dict[str, int] = {}      # metadata of the innermost node span


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        _totals["compiles"] += 1
        _compile_s[0] += duration


def _listen() -> None:
    if not _listening[0]:
        _listening[0] = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def counts() -> Tuple[int, ...]:
    """The process totals of ``COUNTERS``, as a mark for ``book``."""
    _listen()
    return tuple(_totals[k] for k in COUNTERS)


def book(into: Dict[str, int], mark: Tuple[int, ...]) -> None:
    """Add what each counter counted since ``mark`` to ``into``."""
    for k, m in zip(COUNTERS, mark):
        into[k] = into.get(k, 0) + _totals[k] - m


def add(counter: str, n: int) -> None:
    """Count ``n`` more of ``counter``."""
    _totals[counter] += n


def compiles() -> Tuple[int, float]:
    """Programs JAX built so far in this process, and their seconds."""
    _listen()
    return _totals["compiles"], _compile_s[0]


class span:
    """A span that times itself: after ``with span(name, round=r) as sp``
    ``sp.s`` holds its wall seconds. Spans opened inside it carry its
    metadata."""

    __slots__ = ("_name", "_meta", "_outer", "_tm", "_t0", "s")

    def __init__(self, name: str, **meta):
        self._name, self._meta, self.s = name, meta, 0.0

    def __enter__(self) -> "span":
        global _meta
        self._outer = _meta
        _meta = {**_meta, **self._meta}
        self._tm = TraceAnnotation(self._name, **_meta)
        self._tm.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        global _meta
        self.s = time.perf_counter() - self._t0
        self._tm.__exit__(*exc)
        _meta = self._outer


def mark(name: str) -> TraceAnnotation:
    """An untimed span carrying the current node's metadata (more can be
    added with ``set_metadata``)."""
    return TraceAnnotation(name, **_meta)


def spanned(name: str) -> Callable:
    """Decorator: every call of the function inside span ``name``."""
    def deco(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with TraceAnnotation(name, **_meta):
                return fn(*args, **kwargs)
        return call
    return deco


stage = spanned("cohort.stage")     # host->device staging


def launched(jitted) -> Callable:
    """``jitted`` with every call inside a ``cohort.launch`` span;
    ``.lower`` is the jitted function's own."""
    def call(*args):
        with TraceAnnotation("cohort.launch", **_meta):
            return jitted(*args)
    call.lower = jitted.lower
    return call


def fetch(x, side: str = "engine"):
    """``x`` (an array or a tree of them) read to the host as numpy,
    inside ``cohort.fetch`` (``side="engine"``) or ``server.fetch``
    (``side="server"``), counted as one sync. A tree's copies all start
    before the first is waited for."""
    name, counter = _FETCH[side]
    add(counter, 1)
    with TraceAnnotation(name, **_meta):
        if isinstance(x, jax.Array):
            return np.asarray(x)
        return jax.device_get(x)
