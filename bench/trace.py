"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What the chip's trace holds (TPU v5e, JAX 0.9): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per
program run on the chip, named ``jit_<function>(<hash>)``, and whose
line ``XLA Ops`` has the HLO operations inside them.  The host plane
``/host:CPU`` has a line per thread; the Python thread carries the
benchmark's own span around the window and JAX's host events
(``PjitFunction(...)``, ``DevicePut``, ``np.asarray(jax.Array)``).  Host
and device events share one clock.

* window: the host span named ``window_span``.
* busy: the union of the module events' intervals, clipped to the
  window, averaged over the chips.
* kernel time: the summed duration of the module events whose name
  matches ``kernel_pattern`` (a regular expression; the fused-step
  programs, their pad and crop included), clipped to the window and
  averaged over the chips.
* device ops: the ``XLA Ops`` events by module and HLO name, the ten
  that took most time.
* idle gaps: the ten longest intervals of the window with no module
  running, each named by the host event that overlaps it most.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    kernel_s: float
    kernel_calls: int
    chips: int
    top_ops: List[list]
    idle_gaps: List[list]
    lines: List[str]


def _clip(start: float, end: float, win: Interval) -> Optional[Interval]:
    s, e = max(start, win[0]), min(end, win[1])
    return (s, e) if e > s else None


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping ``(start, end)`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: List[Interval], win: Interval) -> List[Interval]:
    """Intervals of ``win`` that no merged ``busy`` interval covers."""
    out, t = [], win[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if win[1] > t:
        out.append((t, win[1]))
    return out


def _short_op(name: str) -> str:
    """``%fused_stencil_band_db.1 = f32[...] custom-call(...)`` -> the
    HLO name, ``fused_stencil_band_db.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    """``jit_concatenate(7752512787538721617)`` -> ``jit_concatenate``."""
    return name.split("(", 1)[0]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _window(planes, window_span: str) -> Interval:
    spans = [(s, e) for p in planes if p.name == HOST_PLANE
             for line in p.lines for name, s, e in _events(line)
             if name == window_span]
    if not spans:
        raise ValueError(f"no host span {window_span!r} in the trace")
    return max(spans, key=lambda iv: iv[1] - iv[0])


def _host_events(planes, window_span: str, win: Interval):
    """Host events inside the window on the thread that holds the
    window span (the benchmark's Python thread)."""
    for p in planes:
        if p.name != HOST_PLANE:
            continue
        for line in p.lines:
            evs = _events(line)
            if any(n == window_span for n, _, _ in evs):
                return [(n, s, e) for n, s, e in evs
                        if n != window_span and _clip(s, e, win)]
    return []


def _name_gap(gap: Interval, host) -> str:
    best, overlap = "no host event", 0.0
    for name, s, e in host:
        iv = _clip(s, e, gap)
        if iv and iv[1] - iv[0] > overlap:
            best, overlap = name, iv[1] - iv[0]
    return best


def reduce_planes(planes, window_span: str,
                  kernel_pattern: str) -> TraceSummary:
    """:func:`reduce_trace` on planes already read (``ProfileData``'s)."""
    planes = list(planes)
    win = _window(planes, window_span)
    kernel = re.compile(kernel_pattern)
    busy_ns, kernel_ns, calls = [], [], 0
    ops_ns: Dict[str, float] = defaultdict(float)
    lines: List[str] = []
    first_busy: List[Interval] = []
    chips = 0
    for p in planes:
        if not DEVICE_PLANE.match(p.name):
            continue
        by_line = {line.name: _events(line) for line in p.lines}
        mods = by_line.get(MODULES, [])
        if not mods:
            continue
        chips += 1
        lines.append(f"{p.name}:{MODULES}")
        clipped = [(n, iv) for n, s, e in mods
                   if (iv := _clip(s, e, win)) is not None]
        merged = union([iv for _, iv in clipped])
        if chips == 1:
            first_busy = merged
        busy_ns.append(sum(e - s for s, e in merged))
        k = [iv for n, iv in clipped if kernel.search(n)]
        kernel_ns.append(sum(e - s for s, e in k))
        calls += len(k)
        starts = [iv[0] for _, iv in clipped]
        for name, s, e in by_line.get(OPS, []):
            iv = _clip(s, e, win)
            if iv is None:
                continue
            i = bisect.bisect_right(starts, iv[0]) - 1
            mod = _module_name(clipped[i][0]) if i >= 0 else "?"
            ops_ns[f"{mod}/{_short_op(name)}"] += iv[1] - iv[0]
    if not chips:
        raise ValueError("no TPU device plane with XLA Modules in the trace")
    host = _host_events(planes, window_span, win)
    idle = sorted(gaps(first_busy, win), key=lambda iv: iv[0] - iv[1])[:TOP]
    return TraceSummary(
        window_s=(win[1] - win[0]) / 1e9,
        busy_s=sum(busy_ns) / chips / 1e9,
        kernel_s=sum(kernel_ns) / chips / 1e9,
        kernel_calls=calls // chips,
        chips=chips,
        top_ops=[[n, t / chips / 1e9] for n, t in
                 sorted(ops_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[_name_gap(g, host), (g[1] - g[0]) / 1e9] for g in idle],
        lines=lines)


def reduce_trace(path: str, window_span: str,
                 kernel_pattern: str) -> TraceSummary:
    """Read the ``.xplane.pb`` at ``path`` and reduce it."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_span,
                         kernel_pattern)
