"""Reduction of a JAX profiler trace (``.xplane.pb``) to each device's op
intervals and the host's named spans.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per HLO op executed, named by the op's instruction text
(``%copy.189 = f32[4097,16,8,1]{...} copy(...)``, parsed by ``hlo.py``);
a loop's event spans the ops of its body.  Host
spans (``jax.profiler.TraceAnnotation``s, such as the benchmark's
``bench.step`` and the engine's ``paged_step``) sit on the host plane's
thread lines.  All times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import glob
import gzip
import os
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"


@dataclass
class Op:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    """What the benchmark reads of one trace: each device's ops, and the
    host spans by name."""
    ops: Dict[str, List[Op]]              # device plane -> ops, by start
    spans: Dict[str, List[Tuple[float, float]]]
    lo: float = 0.0                       # the trace's own extent: first
    hi: float = 0.0                       # and last event, host or device

    @classmethod
    def load(cls, path: str, host_names: Iterable[str] = ()) -> "Trace":
        from jax.profiler import ProfileData
        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb*"),
                                     recursive=True))
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        if path.endswith(".gz"):
            with gzip.open(path, "rb") as f:
                pd = ProfileData.from_serialized_xspace(f.read())
        else:
            pd = ProfileData.from_file(path)
        want = set(host_names)
        ops: Dict[str, List[Op]] = {}
        spans: Dict[str, List[Tuple[float, float]]] = {n: [] for n in want}
        lo, hi = float("inf"), float("-inf")
        for plane in pd.planes:
            if plane.name.startswith(("/device:TPU:", "/host:")):
                for line in plane.lines:
                    for e in line.events:
                        lo = min(lo, e.start_ns)
                        hi = max(hi, e.start_ns + e.duration_ns)
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    evs = [Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                    evs.sort(key=lambda o: o.start)
                    ops[plane.name] = evs
            elif plane.name.startswith("/host:") and want:
                for line in plane.lines:
                    for e in line.events:
                        if e.name in want:
                            spans[e.name].append(
                                (e.start_ns, e.start_ns + e.duration_ns))
        for v in spans.values():
            v.sort()
        if lo > hi:
            lo = hi = 0.0
        return cls(ops, spans, lo, hi)

    def busy(self, plane: str, lo: float = float("-inf"),
             hi: float = float("inf")) -> float:
        """Nanoseconds in ``[lo, hi)`` in which some op ran on ``plane``:
        the union of the op intervals, clipped."""
        return union_length(((max(o.start, lo), min(o.end, hi))
                             for o in self.ops[plane]), presorted=True)


def union_length(intervals: Iterable[Tuple[float, float]],
                 presorted: bool = False) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    iv = [(a, b) for a, b in intervals if b > a]
    if not presorted:
        iv.sort()
    total, cur_a, cur_b = 0.0, None, None
    for a, b in iv:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def idle_gaps(ops: List[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of ``[lo, hi)`` in which no op ran, longest first."""
    gaps, cur = [], lo
    for o in ops:
        if o.end <= lo or o.start >= hi:
            continue
        if o.start > cur:
            gaps.append((cur, o.start))
        cur = max(cur, o.end)
    if cur < hi:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps


def covering(spans: Dict[str, List[Tuple[float, float]]], t: float
             ) -> Optional[str]:
    """The innermost (shortest) named host span covering time ``t``."""
    best, best_len = None, float("inf")
    for name, iv in spans.items():
        for a, b in iv:
            if a <= t < b and b - a < best_len:
                best, best_len = name, b - a
    return best
