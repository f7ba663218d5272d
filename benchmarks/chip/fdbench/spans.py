"""The program's own spans (``repro.common.tracing``) in the traced window.

The harness keeps the device ops and its own round and step spans
(``xplane.Trace``); the program's spans sit on the same host plane and the
same clock. This module opens the trace file again, keeps every program
span inside the window (name, start, end, round), and nests them. A trace
of a program that opens no such spans yields none, and the readers then
return nothing.
"""
from __future__ import annotations

import bisect
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from fdbench import xplane

TRACES = Path(__file__).resolve().parents[2] / "results" / "trace"
NAMES = ("sched.step", "server.ingest", "server.aggregate", "server.fetch",
         "cohort.plan", "cohort.stage", "cohort.launch", "cohort.fetch")
PHASE = "phase."
NO_SPAN = "no program span"

_last: List = [None, None]      # (trace, its Spans): one trace per run


class Span:
    __slots__ = ("name", "start", "end", "round", "children")

    def __init__(self, name: str, start: int, end: int, rnd):
        self.name, self.start, self.end, self.round = name, start, end, rnd
        self.children: List["Span"] = []

    @property
    def ns(self) -> int:
        return self.end - self.start


class Spans:
    """Program spans of one trace inside ``trace``'s window, nested."""

    def __init__(self, trace: xplane.Trace,
                 events: Sequence[Tuple[str, int, int, Optional[int]]]):
        self.trace = trace
        self.all = [Span(n, a, b, r) for n, a, b, r in sorted(
            events, key=lambda e: (e[1], -e[2]))
            if a >= trace.lo and b <= trace.hi]
        self.top: List[Span] = []
        stack: List[Span] = []
        for s in self.all:
            while stack and stack[-1].end <= s.start:
                stack.pop()
            (stack[-1].children if stack else self.top).append(s)
            stack.append(s)

    @classmethod
    def from_profile(cls, trace: xplane.Trace, profile) -> "Spans":
        events = []
        for plane in profile.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in NAMES or e.name.startswith(PHASE):
                        rnd = dict(e.stats).get("round")
                        events.append((e.name, int(e.start_ns),
                                       int(e.start_ns + e.duration_ns),
                                       None if rnd is None else int(rnd)))
        return cls(trace, events)

    @property
    def rounds(self) -> int:
        return len(self.trace.rounds)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.all if s.name == name]

    def per_round_s(self, name: str) -> Optional[float]:
        """Seconds in spans ``name`` per traced round (None: no such
        span)."""
        got = self.named(name)
        if not got:
            return None
        return sum(s.ns for s in got) * 1e-9 / self.rounds

    def per_round_count(self, name: str) -> Optional[float]:
        got = self.named(name)
        return len(got) / self.rounds if got else None

    def own_s(self, name: str) -> Optional[float]:
        """Seconds per round in spans ``name`` less their child spans."""
        got = self.named(name)
        if not got:
            return None
        return sum(s.ns - sum(c.ns for c in s.children)
                   for s in got) * 1e-9 / self.rounds

    # ---------------------------------------------------------------- idle
    def _idle(self) -> Tuple[List[int], List[int], List[int]]:
        """The first chip's idle intervals in the window, as starts, ends
        and the running sum of their lengths."""
        t = self.trace
        busy = t.busy_intervals(sorted(t.device_ops)[0])
        edges = [t.lo] + [x for iv in busy for x in iv] + [t.hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        acc = [0]
        for a, b in gaps:
            acc.append(acc[-1] + b - a)
        return [a for a, _ in gaps], [b for _, b in gaps], acc

    def idle_ns(self, lo: int, hi: int, idle=None) -> int:
        """Nanoseconds of device idle time inside ``[lo, hi]``."""
        starts, ends, acc = idle or self._idle()
        i = bisect.bisect_right(ends, lo)         # first gap ending after lo
        j = bisect.bisect_left(starts, hi)        # gaps starting before hi
        if i >= j:
            return 0
        total = acc[j] - acc[i]
        total -= max(0, lo - starts[i])
        total -= max(0, ends[j - 1] - hi)
        return total

    def idle_by_span(self) -> Tuple[Dict[str, float], float]:
        """Idle seconds by the innermost program span they fell in, with
        ``NO_SPAN`` for idle time inside the harness's round spans and no
        program span; and the idle seconds inside the round spans."""
        idle = self._idle()
        out: Dict[str, float] = {}
        inside = sum(self.idle_ns(a, b, idle) for a, b in self.trace.rounds)

        def walk(s: Span) -> int:
            held = self.idle_ns(s.start, s.end, idle)
            own = held - sum(walk(c) for c in s.children)
            out[s.name] = out.get(s.name, 0.0) + own * 1e-9
            return held

        in_spans = 0
        for s in self.top:
            in_spans += sum(self.idle_ns(max(s.start, a), min(s.end, b),
                                         idle)
                            for a, b in self.trace.rounds
                            if s.start < b and a < s.end)
            walk(s)
        out[NO_SPAN] = (inside - in_spans) * 1e-9
        return out, inside * 1e-9

    def idle_in_s(self, names: Sequence[str]) -> Optional[float]:
        """Idle seconds per round inside any span named in ``names``."""
        got = [(s.start, s.end) for s in self.all if s.name in names]
        if not got:
            return None
        idle = self._idle()
        return sum(self.idle_ns(a, b, idle)
                   for a, b in xplane.union(got)) * 1e-9 / self.rounds


def trace_files() -> List[Path]:
    """Every trace the harness wrote, newest first."""
    files = TRACES.glob("*/plugins/profile/*/*.xplane.pb")
    return sorted(files, key=lambda p: p.stat().st_mtime, reverse=True)


def of(ctx) -> Optional[Spans]:
    """The program spans of the run's traced window, or None when the run
    was not traced or its program opens no spans. The trace file is the
    newest one the harness wrote whose first round span starts where the
    window does."""
    trace = getattr(ctx, "trace", None)
    if trace is None:
        return None
    if _last[0] is not trace:
        from jax.profiler import ProfileData
        found = Spans(trace, [])
        for path in trace_files():
            profile = ProfileData.from_file(str(path))
            if _first_round_start(profile) == trace.lo:
                found = Spans.from_profile(trace, profile)
                break
        else:
            print("spans: no trace file matches the traced window",
                  file=sys.stderr)
        _last[:] = [trace, found]
    got = _last[1]
    return got if got.all else None


def _first_round_start(profile) -> Optional[int]:
    starts = [int(e.start_ns) for plane in profile.planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == xplane.ROUND_SPAN]
    return min(starts) if starts else None
