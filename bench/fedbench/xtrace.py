"""From a profiler trace (.xplane.pb) to the events the metric readers
use: each chip's XLA module runs, each chip's XLA ops where the trace
holds them, and the benchmark's own host spans (`bench.*`), all on the
profiler's clock in nanoseconds."""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_HASH = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclass
class Trace:
    chips: int
    # per chip: (module name without its hash, start_ns, end_ns)
    modules: List[List[Tuple[str, float, float]]]
    # per chip: (op name, start_ns, end_ns); empty when not traced
    ops: List[List[Tuple[str, float, float]]]
    spans: Dict[str, List[Interval]] = field(default_factory=dict)

    @property
    def window(self) -> Interval:
        (w,) = self.spans[WINDOW_SPAN]
        return w

    @property
    def window_s(self) -> float:
        a, b = self.window
        return (b - a) / 1e9


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    return paths[0]


def load(path: str, chips: int) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: List[list] = [[] for _ in range(chips)]
    ops: List[list] = [[] for _ in range(chips)]
    spans: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < chips:
            i = int(m.group(1))
            for line in plane.lines:
                dest = {"XLA Modules": modules[i],
                        "XLA Ops": ops[i]}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append((_HASH.sub("", e.name), e.start_ns,
                                 e.end_ns))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    if not any(modules):
        raise RuntimeError("the trace holds no device module on the chips "
                           "used")
    for lst in spans.values():
        lst.sort()
    return Trace(chips, modules, ops, spans)


def clip(intervals, window: Interval) -> List[Interval]:
    a, b = window
    return [(max(s, a), min(e, b)) for s, e in intervals if e > a and s < b]


def union(intervals) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_ns(trace: Trace, chip: int) -> float:
    """Union of the chip's module runs inside the window."""
    return total(union(clip([(s, e) for _, s, e in trace.modules[chip]],
                            trace.window)))


def module_ns(trace: Trace, chip: int, pattern: str) -> List[float]:
    """Durations of the window's runs of modules whose name matches."""
    rx = re.compile(pattern)
    return [e - s for n, s, e in trace.modules[chip]
            if rx.fullmatch(n) and s >= trace.window[0]
            and e <= trace.window[1]]


def span_ns(trace: Trace, name: str) -> Optional[float]:
    """Summed duration of a host span inside the window (nested calls of
    the same span counted once), or None when the span never ran."""
    ivs = trace.spans.get(name)
    if not ivs:
        return None
    return total(union(clip(ivs, trace.window)))


def idle_gaps(trace: Trace, chip: int) -> List[Interval]:
    """Stretches of the window in which the chip ran no module."""
    a, b = trace.window
    gaps, t = [], a
    for s, e in union(clip([(s, e) for _, s, e in trace.modules[chip]],
                           trace.window)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if b > t:
        gaps.append((t, b))
    return gaps


def innermost_span(trace: Trace, t: float) -> str:
    """Name of the shortest benchmark span that holds time t."""
    best, best_len = WINDOW_SPAN, float("inf")
    for name, ivs in trace.spans.items():
        for s, e in ivs:
            if s <= t < e and e - s < best_len:
                best, best_len = name, e - s
    return best


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Device ops (or modules, where ops were not traced) by time, and
    idle time by the host span it fell in, each averaged over chips."""
    per_op: Dict[str, float] = {}
    per_gap: Dict[str, float] = {}
    for chip in range(trace.chips):
        events = trace.ops[chip] or trace.modules[chip]
        for n, s, e in clip_named(events, trace.window):
            key = n.split(" = ")[0]
            per_op[key] = per_op.get(key, 0.0) + (e - s)
        for s, e in idle_gaps(trace, chip):
            key = innermost_span(trace, (s + e) / 2)
            per_gap[key] = per_gap.get(key, 0.0) + (e - s)

    def top_list(d):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / 1e9 / trace.chips] for k, v in items]

    return {"device_ops": top_list(per_op), "idle_gaps": top_list(per_gap)}


def clip_named(events, window: Interval):
    a, b = window
    return [(n, max(s, a), min(e, b)) for n, s, e in events
            if e > a and s < b]
