"""Device and host time by the program's own names, from a traced run.

The engine names its device work with ``jax.named_scope``
(``repro.obs.scopes``: ``superstep.step``, ``localops.spmv_pull``,
``ell_in.b3``, ...) and its host phases as ``repro.*`` profiler spans
(``repro.graph.ell``, ``repro.engine.call``, ...).  A device trace names
each operation only by its HLO instruction (``fusion.82``), so the
device side is joined through the scope map of the executables the
engine compiled in this process (``repro.obs.compiled_scopes``): each
``XLA Ops`` event is put in the ``XLA Modules`` event around it and
looked up in that module's map.

``reading(run)`` reduces the trace a traced run of ``run.py`` leaves in
``artifacts/trace`` to a :class:`Reading`, once per run, and writes it
beside the trace as ``reading.json`` with the scope maps in
``scope_map.json``.  Against a program that names nothing (no
``compiled_scopes``, no ``repro.*`` span) the parts it lacks are None
or empty, and the metrics that read them read None.
"""

from __future__ import annotations

import bisect
import json
import re
from dataclasses import asdict, dataclass, field
from pathlib import Path

import xplane

# where run.py's traced runs write their profiler trace
TRACE_DIR = Path(__file__).resolve().parent / "artifacts" / "trace"
PROGRAM_PREFIX = "repro."
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"


@dataclass
class Reading:
    # device self time per scope path in the window, mean over devices;
    # None where the process kept no scope map
    scope_s: dict | None
    # repro.* host spans: [[name, start_ns, end_ns], ...] in start order
    program_spans: list = field(default_factory=list)
    device_scopes: list = field(default_factory=list)   # top scope_s
    idle_spans: list = field(default_factory=list)      # [[span, s], ...]

    def under(self, component_prefix: str) -> float | None:
        """Seconds under any scope path with a component starting with
        ``component_prefix`` (``"localops."``)."""
        if self.scope_s is None:
            return None
        return sum(t for path, t in self.scope_s.items()
                   if any(part.startswith(component_prefix)
                          for part in path.split("/")))

    def span_s(self, name: str) -> float | None:
        """Wall seconds of the program span ``repro.<name>``, summed
        over its occurrences; None where the trace has none."""
        hits = [e - s for n, s, e in self.program_spans
                if n == PROGRAM_PREFIX + name]
        return sum(hits) * 1e-9 if hits else None


def module_of(event_name: str) -> str:
    """``jit_fn(1934763232443944729)`` -> ``jit_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _device_lines(pd):
    """Per device plane: (modules, ops), each [(name, start, end)]."""
    out = []
    for plane in pd.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if xplane.OPS_LINE not in lines:
            continue
        mods = sorted(((module_of(ev.name), ev.start_ns, ev.end_ns)
                       for ev in lines[MODULES_LINE].events),
                      key=lambda m: m[1]) if MODULES_LINE in lines else []
        ops = [(xplane.op_name(ev.name), ev.start_ns, ev.end_ns)
               for ev in lines[xplane.OPS_LINE].events]
        out.append((mods, ops))
    return out


def _spans(pd, prefixes):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    return sorted(spans, key=lambda s: s[1])


def reduce(pd, scope_maps: dict | None, window: str = "window",
           top: int = 10) -> Reading:
    """The run's reading from a profile.  ``scope_maps`` is ``{module
    name: {instruction: scope path}}``; None, or a profile without a
    window span or device operations, leaves ``scope_s`` None."""
    spans = _spans(pd, (xplane.SPAN_PREFIX, PROGRAM_PREFIX))
    program = [[n, s, e] for n, s, e in spans
               if n.startswith(PROGRAM_PREFIX)]
    win = [(s, e) for n, s, e in spans if n == xplane.SPAN_PREFIX + window]
    devices = _device_lines(pd)
    if not win or not devices:
        return Reading(scope_s=None, program_spans=program)
    w0, w1 = win[0]
    totals, gaps = {}, []
    bounds = {t for _, s, e in spans for t in (s, e)}
    for mods, ops in devices:
        starts = [s for _, s, _ in mods]
        by_module = {}
        for name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0] if i >= 0 and s <= mods[i][2] else ""
            by_module.setdefault(mod, []).append(
                (name, max(s, w0), min(e, w1)))
        for mod, clipped in by_module.items():
            scopes = (scope_maps or {}).get(mod, {})
            for name, t in xplane.self_times(clipped).items():
                path = scopes.get(name, UNSCOPED)
                totals[path] = totals.get(path, 0.0) + t
        merged = xplane._merge([(s, e) for evs in by_module.values()
                                for _, s, e in evs])
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            cuts = [s] + sorted(t for t in bounds if s < t < e) + [e]
            gaps += [(b - a, xplane._label(spans, (a + b) / 2))
                     for a, b in zip(cuts, cuts[1:]) if b > a]
    ndev = len(devices)
    scope_s = None if scope_maps is None else \
        {path: t / ndev * 1e-9 for path, t in totals.items()}
    ranked = sorted((scope_s or {}).items(), key=lambda kv: -kv[1])
    return Reading(
        scope_s=scope_s, program_spans=program,
        device_scopes=[[p, t] for p, t in ranked[:top]],
        idle_spans=[[name, d * 1e-9] for d, name in
                    sorted(gaps, reverse=True)[:top]])


def compiled_scope_maps() -> dict | None:
    """The scope maps of what the engine compiled in this process, or
    None where the program keeps none."""
    try:
        from repro.obs import compiled_scopes
    except ImportError:
        return None
    return compiled_scopes() or None


# the last run read, by its trace reduction, and its reading
_LAST: list = [None, None]


def reading(run) -> Reading | None:
    """The reading of ``run``'s trace, read from ``TRACE_DIR`` once per
    run and written beside the trace; None where the run has no trace
    (untraced, or no device operation in it), since ``TRACE_DIR`` then
    holds an earlier run's trace or none."""
    if run.trace is None:
        return None
    if _LAST[0] is not run.trace:
        found = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        maps = compiled_scope_maps()
        got = reduce(xplane.load(found[-1]), maps) if found else None
        if got is not None:
            (TRACE_DIR / "reading.json").write_text(json.dumps(asdict(got)))
            (TRACE_DIR / "scope_map.json").write_text(json.dumps(maps))
        _LAST[:] = [run.trace, got]
    return _LAST[1]
