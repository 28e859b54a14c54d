"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers: busy and idle time in the window, the device operations
that took most time, and the longest idle gaps with the host span that
was open during each.

Busy time is the union of the intervals of the ``XLA Ops`` events on
each device plane (ops nest: a ``while`` holds its body's ops), clipped to the window (the host span named
``window``), averaged over the devices.  An idle gap is cut where a
host span opens or closes, and each piece is named by the innermost
benchmark span open through it (``launch``, or ``window`` between
launches).
"""

from __future__ import annotations

import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the devices
    devices: int
    device_ops: list                    # [[name, seconds], ...] top 10
    idle_gaps: list                     # [[host span, seconds], ...]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def host_spans(pd) -> list[tuple[str, float, float]]:
    """Every benchmark span on the host planes: (name, start, end) ns."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):],
                                  ev.start_ns, ev.end_ns))
    return spans


def op_name(event_name: str) -> str:
    """``%fusion.14 = (u32[1]...) fusion(...)`` -> ``fusion.14``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_ops(pd) -> dict[str, list[tuple[str, float, float]]]:
    """Per device plane, its ``XLA Ops`` events as (name, start, end)."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [(op_name(ev.name), ev.start_ns,
                                    ev.end_ns) for ev in line.events]
    return out


def self_times(ops) -> dict[str, float]:
    """Time per op name, less the time of the ops nested inside it (a
    ``while`` holds its body's ops on the same line)."""
    totals = {}
    stack = []                       # open [name, start, end, nested]

    def close():
        name, s, e, nested = stack.pop()
        totals[name] = totals.get(name, 0.0) + (e - s) - nested
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and not (s >= stack[-1][1] and e <= stack[-1][2]):
            close()
        stack.append([name, s, e, 0.0])
    while stack:
        close()
    return totals


def _label(spans, t):
    """Innermost benchmark span open at time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


def reduce(pd, window: str = "window", top: int = 10) -> Reduced | None:
    """The window's device numbers, or None when the trace holds no
    window span or no device operation."""
    spans = host_spans(pd)
    win = [(s, e) for name, s, e in spans if name == window]
    ops = device_ops(pd)
    if not win or not ops:
        return None
    w0, w1 = win[0]
    bounds = {t for _, s, e in spans for t in (s, e)}
    busy, totals, gaps = [], {}, []
    for evs in ops.values():
        clipped = [(name, max(s, w0), min(e, w1)) for name, s, e in evs
                   if e > w0 and s < w1]
        for name, t in self_times(clipped).items():
            totals[name] = totals.get(name, 0.0) + t
        merged = _merge([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            # a gap is cut where a host span opens or closes, so each
            # piece is named by the one span open all through it
            cuts = [s] + sorted(t for t in bounds if s < t < e) + [e]
            gaps += [(b - a, _label(spans, (a + b) / 2))
                     for a, b in zip(cuts, cuts[1:]) if b > a]
    ndev = len(ops)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / ndev * 1e-9,
        devices=ndev,
        device_ops=[[name, t / ndev * 1e-9] for name, t in ranked],
        idle_gaps=[[name, d * 1e-9] for d, name in sorted(gaps,
                                                         reverse=True)[:top]])


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))
