"""Reduce a JAX profiler trace to device busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and nothing else. Device planes are named
``/device:TPU:<n>``; the operations that ran on a device are the events
of its ``XLA Ops`` line. Host threads are the lines of ``/host:CPU``.
The traced slice is the host span ``bench_traced_slice`` that the
generator opens around it.

    python bench/trace_reduce.py <trace dir>    # summary of one trace
"""
from __future__ import annotations

import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
SLICE_SPAN = "bench_traced_slice"

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    return ProfileData.from_file(path)


def stats_of(obj) -> Dict[str, object]:
    try:
        return {k: v for k, v in obj.stats}
    except (TypeError, ValueError):
        return {}


def device_planes(pd) -> List:
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    return sorted(planes, key=lambda p: int(DEVICE_PLANE.match(p.name)[1]))


def op_events(plane) -> List:
    for line in plane.lines:
        if line.name == OPS_LINE:
            return list(line.events)
    return []


def clip(events: Iterable, t0: int, t1: int) -> List[Interval]:
    out = []
    for e in events:
        s, end = max(int(e.start_ns), t0), min(int(e.end_ns), t1)
        if end > s:
            out.append((s, end))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def slice_bounds(pd) -> Tuple[int, int]:
    """(start, end) ns of the generator's traced slice."""
    for p in pd.planes:
        if p.name != HOST_PLANE:
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == SLICE_SPAN:
                    return int(e.start_ns), int(e.end_ns)
    raise ValueError(f"trace holds no {SLICE_SPAN!r} span")


HLO_TEXT = re.compile(r"^(%\S+) = (\S+?)(?:\{[^ ]*\})? (?:[a-z]+\[[^ ]*\] )*"
                      r"([a-z][a-z0-9_-]*)\(")
TUPLE_TEXT = re.compile(r"^(%\S+) = \(.*?\) ([a-z][a-z0-9_-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """An op event's HLO text cut to its instruction name, operation and
    result shape (and the custom-call target)."""
    m = HLO_TEXT.match(name)
    if not m:
        t = TUPLE_TEXT.match(name)
        return f"{t[1]} {t[2]} (tuple)" if t else name[:120]
    out = f"{m[1]} {m[3]} {m[2][:60]}"
    t = TARGET.search(name)
    return out + (f" {t[1]}" if t else "")


def event_matches(e, pattern: re.Pattern) -> bool:
    if pattern.search(e.name):
        return True
    return any(isinstance(v, str) and pattern.search(v)
               for v in stats_of(e).values())


class Summary:
    """Per-device busy intervals and op events inside the traced slice."""

    def __init__(self, pd, bounds: Optional[Interval] = None):
        self.pd = pd
        self.t0, self.t1 = bounds or slice_bounds(pd)
        self.devices = device_planes(pd)
        if not self.devices:
            raise ValueError("trace holds no TPU device plane")
        self.ops = [[e for e in op_events(p)
                     if e.end_ns > self.t0 and e.start_ns < self.t1]
                    for p in self.devices]
        self.busy = [union(clip(evs, self.t0, self.t1)) for evs in self.ops]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> List[float]:
        return [total(b) * 1e-9 for b in self.busy]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def kernel_events(self, pattern: str) -> List[List]:
        rx = re.compile(pattern)
        return [[e for e in evs if event_matches(e, rx)] for evs in self.ops]

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of a kernel's events, summed over devices."""
        return sum(total(union(clip(evs, self.t0, self.t1)))
                   for evs in self.kernel_events(pattern)) * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """Device ops that took most time, averaged over devices."""
        acc: Dict[str, float] = {}
        for evs in self.ops:
            for s, e, name in ((max(int(x.start_ns), self.t0),
                                min(int(x.end_ns), self.t1), x.name)
                               for x in evs):
                if e > s:
                    acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
        k = len(self.ops)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[short_name(name), s / k] for name, s in top]

    def gaps(self, device: int = 0) -> List[Interval]:
        """Idle intervals of one device inside the slice."""
        out, t = [], self.t0
        for s, e in self.busy[device]:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.t1 > t:
            out.append((t, self.t1))
        return out

    def host_events(self) -> List:
        evs = []
        for p in self.pd.planes:
            if p.name == HOST_PLANE:
                for line in p.lines:
                    evs += [e for e in line.events if e.name != SLICE_SPAN
                            and e.end_ns > self.t0
                            and e.start_ns < self.t1]
        return evs

    def idle_gaps(self, n: int = 10, device: int = 0) -> List[List]:
        """The longest idle gaps, each named by what the host was doing:
        the shortest host event that covers at least half of the gap, else
        the host event that overlaps it most."""
        host = self.host_events()
        out = []
        for s, e in sorted(self.gaps(device), key=lambda g: g[0] - g[1])[:n]:
            best, best_key = "(no host event)", None
            for h in host:
                ov = min(e, int(h.end_ns)) - max(s, int(h.start_ns))
                if ov <= 0:
                    continue
                covers = 2 * ov >= e - s
                key = ((0, int(h.end_ns) - int(h.start_ns)) if covers
                       else (1, -ov))
                if best_key is None or key < best_key:
                    best, best_key = h.name, key
            out.append([best, (e - s) * 1e-9])
        return out


def describe(pd, limit: int = 12) -> str:
    """Planes, lines, event counts, the most frequent names and the stats
    of a few events: what to look at before matching on names."""
    lines = []
    for p in pd.planes:
        lines.append(f"plane {p.name!r} stats={list(stats_of(p))[:8]}")
        for line in p.lines:
            evs = list(line.events)
            names: Dict[str, int] = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            lines.append(f"  line {line.name!r} events={len(evs)} top={top}")
            for e in evs[:2]:
                lines.append(f"    e {e.name!r} {e.start_ns} {e.duration_ns} "
                             f"{ {k: str(v)[:160] for k, v in stats_of(e).items()} }")
    return "\n".join(lines)


if __name__ == "__main__":
    print(describe(load(sys.argv[1])))
