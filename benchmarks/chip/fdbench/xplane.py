"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The harness wraps every traced round in a host span ``fdbench.round`` and
every ``RoundScheduler.step()`` call in a span ``fdbench.step``; it knows
the phase step k returned, and pairs the k-th step span with it. From the
device planes (``/device:TPU:<n>``) only the ``XLA Ops`` line is read: each
event is one HLO operation, named by its HLO text
(``%<instruction> = <shape> <opcode>(...)``), on the host's clock.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ROUND_SPAN = "fdbench.round"
STEP_SPAN = "fdbench.step"
OUTSIDE = "outside any step"
_SHAPE = re.compile(r"=\s*\(?\s*[a-z0-9]+\[([0-9,]*)\]")

Interval = Tuple[int, int]


def instruction(event_name: str) -> str:
    """``%fusion.3 = f32[..] fusion(..)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def result_dims(event_name: str) -> Tuple[int, ...]:
    """Dimensions of the op's (first) result shape, from its HLO text."""
    m = _SHAPE.search(event_name)
    if not m or not m.group(1):
        return ()
    return tuple(int(v) for v in m.group(1).split(","))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


class Trace:
    """The parts of one trace the metrics read; times in nanoseconds."""

    def __init__(self, device_ops: Dict[str, List[Tuple[str, int, int]]],
                 rounds: List[Interval], steps: List[Interval]):
        self.device_ops = device_ops          # plane -> [(name, start, end)]
        self.rounds = sorted(rounds)
        self.steps = sorted(steps)
        if not self.rounds:
            raise ValueError(f"trace holds no {ROUND_SPAN!r} span")
        if not self.device_ops:
            raise ValueError("trace holds no TPU device plane")
        self.lo = self.rounds[0][0]
        self.hi = self.rounds[-1][1]

    @classmethod
    def from_profile(cls, profile) -> "Trace":
        """``profile``: a ``jax.profiler.ProfileData``."""
        device_ops, rounds, steps = {}, [], []
        for plane in profile.planes:
            if DEVICE_PLANE.match(plane.name):
                ops = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops.extend((e.name, int(e.start_ns),
                                    int(e.start_ns + e.duration_ns))
                                   for e in line.events)
                device_ops[plane.name] = ops
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == ROUND_SPAN:
                            rounds.append((int(e.start_ns),
                                           int(e.start_ns + e.duration_ns)))
                        elif e.name == STEP_SPAN:
                            steps.append((int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
        return cls(device_ops, rounds, steps)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(str(path)))

    # ----------------------------------------------------------- busy, idle
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_intervals(self, plane: str) -> List[Interval]:
        return union(_clip([(a, b) for _, a, b in self.device_ops[plane]],
                           self.lo, self.hi))

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        tot = [sum(b - a for a, b in self.busy_intervals(p))
               for p in self.device_ops]
        return sum(tot) / len(tot) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    # ------------------------------------------------------------- top ops
    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device operations by self time (an op's time less that of the
        ops it encloses, such as a while loop's body), first chip."""
        plane = sorted(self.device_ops)[0]
        evs = sorted(((a, b, nm) for nm, a, b in self.device_ops[plane]
                      if b > self.lo and a < self.hi),
                     key=lambda e: (e[0], -e[1]))
        self_ns: Dict[str, int] = {}
        stack: List[List] = []          # [end, name, start, child ns]

        def close(entry):
            end, name, start, child = entry
            self_ns[name] = self_ns.get(name, 0) + (end - start) - child
            if stack:
                stack[-1][3] += end - start

        for a, b, nm in evs:
            a, b = max(a, self.lo), min(b, self.hi)
            while stack and stack[-1][0] <= a:
                close(stack.pop())
            stack.append([b, instruction(nm), a, 0])
        while stack:
            close(stack.pop())
        top = sorted(self_ns.items(), key=lambda kv: -kv[1])[:n]
        return [(name, ns * 1e-9) for name, ns in top]

    # ----------------------------------------------------------- idle gaps
    def labelled_gaps(self, phases: Sequence[str]) -> List[Tuple[str, float]]:
        """Every idle gap of the first chip inside the window, longest
        first, named by the phase whose step span holds the gap's middle
        (the k-th step span carries ``phases[k]``)."""
        if len(phases) != len(self.steps):
            raise ValueError(f"{len(self.steps)} step spans but "
                             f"{len(phases)} phases")
        busy = self.busy_intervals(sorted(self.device_ops)[0])
        edges = [self.lo] + [t for iv in busy for t in iv] + [self.hi]
        starts = [s0 for s0, _ in self.steps]
        gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            k = bisect.bisect_right(starts, mid) - 1
            label = (phases[k] if k >= 0 and mid < self.steps[k][1]
                     else OUTSIDE)
            gaps.append((label, (b - a) * 1e-9))
        return sorted(gaps, key=lambda g: -g[1])

    # ------------------------------------------------------------- kernels
    def kernel_calls(self, pattern: str) -> List[Tuple[str, float]]:
        """``(HLO text, seconds)`` of every op on every chip inside the
        window whose instruction name matches ``pattern``."""
        rx = re.compile(pattern)
        out = []
        for ops in self.device_ops.values():
            for nm, a, b in ops:
                if b > self.lo and a < self.hi and rx.search(instruction(nm)):
                    out.append((nm, (min(b, self.hi) - max(a, self.lo))
                                * 1e-9))
        return out


def breakdown(trace: Trace, phases: Sequence[str]) -> Dict:
    return {"device_ops": [[n, s] for n, s in trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in
                          trace.labelled_gaps(phases)[:10]]}


def gap_seconds_by_phase(trace: Trace, phases: Sequence[str]
                         ) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for label, s in trace.labelled_gaps(phases):
        out[label] = out.get(label, 0.0) + s
    return out

