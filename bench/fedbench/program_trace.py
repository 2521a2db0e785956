"""The program's own spans, from the traced window's profile, for the
per-layer metrics that read them.

The program (src/repro/core/spans.py) puts `fl.*` host spans around each
layer of the round, with host-known stats (round, clients, bytes, ...).
This module reads them from the same .xplane.pb as `xtrace.load`, so
their times are on the clock of `ctx.trace`.

A program without these spans (an older build) yields none; its readers
then return None and the result line leaves their metrics out.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from . import xtrace

SPAN_PREFIX = "fl."

Interval = Tuple[float, float]


@dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict


@dataclass
class ProgramTrace:
    # name -> the span's events, by start
    spans: Dict[str, List[Span]] = field(default_factory=dict)

    def intervals(self, name: str) -> List[Interval]:
        return [(s.start, s.end) for s in self.spans.get(name, [])]


@functools.lru_cache(maxsize=2)
def load(path: str) -> ProgramTrace:
    """The `fl.*` spans of one trace file, read once per process and
    path."""
    from jax.profiler import ProfileData
    out = ProgramTrace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.spans.setdefault(e.name, []).append(
                        Span(e.name, e.start_ns, e.end_ns, dict(e.stats)))
    for lst in out.spans.values():
        lst.sort(key=lambda s: s.start)
    return out


def of(ctx) -> ProgramTrace:
    """The program's spans in the run whose reduced trace is `ctx.trace`."""
    from . import harness
    return load(xtrace.find_xplane(str(harness.TRACE_DIR)))


def span_union(pt: ProgramTrace, names, window: Interval) -> List[Interval]:
    """Union of the named spans' events, clipped to the window."""
    ivs = [iv for n in names for iv in pt.intervals(n)]
    return xtrace.union(xtrace.clip(ivs, window))


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def self_ns(pt: ProgramTrace, outer: str, children, window: Interval):
    """Time inside `outer` spans less the time inside any of `children`
    that falls inside them, clipped to the window; None when `outer`
    never ran there."""
    out = span_union(pt, [outer], window)
    if not out:
        return None
    inner = intersect(out, span_union(pt, children, window))
    return xtrace.total(out) - xtrace.total(inner)
