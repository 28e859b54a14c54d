"""Declared observability vocabulary + the runtime instrument registry.

Everything the subsystem can emit is declared HERE, statically:

  * ``COMPONENTS`` — the tracks a span/event can land on.  The trace
    exporter gives each component its own ``tid`` in declaration
    order, so track layout in Perfetto is stable across runs.
  * ``SPAN_KINDS`` — every interval kind, its canonical component, its
    Chrome export shape (``complete`` -> one "X" event; ``async`` ->
    "b"/"e" pairs, used for kinds that OVERLAP on one track: a query's
    lifetime brackets many nested server spans, device launches
    double-buffer), and a one-line description.
  * ``EVENT_KINDS`` — instant events ("i" in the export).
  * ``INSTRUMENTS`` — the counters/gauges/histograms the roll-up
    report knows about.  ``Registry`` refuses undeclared names, so an
    instrumentation site cannot silently invent a metric the docs
    table doesn't list.
  * ``DEVICE_SCOPES`` — the ``jax.named_scope`` names the engine wraps
    its device work in (``obs/scopes.py``); they reach the compiled
    HLO as ``op_name`` metadata, where ``op_scopes`` reads them back.
  * ``PROGRAM_SPANS`` — the host phases the engine annotates on the
    profiler's clock as ``repro.<name>`` (``obs/spans.annotate``).
    Serve spans reach the profiler too, as
    ``repro.<component>.<kind>``.

A ``<placeholder>`` in a scope or span name stands for one identifier
(``<ell>.b<i>`` covers ``ell_in.b3``); ``declared()`` matches names
against them and the annotation helpers refuse any other name.

``docs/API.md``'s Observability tables are generated by
``spans_markdown_table()`` / ``instruments_markdown_table()`` /
``scopes_markdown_table()`` and drift-tested with the same ``table in
docs`` idiom as the algorithm and crash-point tables.
"""

from __future__ import annotations

import functools
import re

import numpy as np

# component -> description (declaration order = exported tid order)
COMPONENTS = {
    "server": "GraphServer request path (admission, demux, mutation)",
    "coalescer": "bucket-ladder batch formation",
    "executor": "double-buffered executor host side",
    "device": "device launches (dispatch -> done)",
    "durability": "WAL appends and snapshot writes",
    "recovery": "checkpoint runner (chunks, detections, rollbacks)",
}

# kind -> (component, export, description); export in {complete, async}
SPAN_KINDS = {
    "query": ("server", "async",
              "whole query lifetime, submit -> resolve; args carry the "
              "exact latency_s ServeMetrics recorded"),
    "admission": ("server", "complete",
                  "submit_query: stamp, validate, hand to coalescer"),
    "validate": ("server", "complete",
                 "root/weight validation inside admission"),
    "coalesce_wait": ("coalescer", "async",
                      "batch formation: first admit -> batch formed"),
    "dispatch": ("executor", "complete",
                 "host-side build + dispatch of a formed batch"),
    "device": ("device", "async",
               "device launch in flight: t_dispatch -> t_done"),
    "demux": ("server", "complete",
              "launch result demux + per-query resolve"),
    "mutation": ("server", "complete",
                 "mutate(): barrier flush, apply, snapshot check"),
    "wal_append": ("durability", "complete",
                   "write-ahead-log record append (incl. flush)"),
    "snapshot": ("durability", "complete",
                 "crash-consistent snapshot write"),
    "recovery": ("server", "complete",
                 "GraphServer.recover(): snapshot load + WAL replay"),
    "chunk": ("recovery", "complete",
              "one checkpointed chunk of rounds in CheckpointRunner"),
}

# instant events: kind -> (component, description)
EVENT_KINDS = {
    "fault_detection": ("recovery",
                        "guard tripped; chunk result discarded"),
    "rollback": ("recovery", "carry restored from last checkpoint"),
    "checkpoint": ("recovery", "chunk committed as a new checkpoint"),
    "shed": ("server", "query shed at admission (queue full)"),
    "rejected": ("server", "query rejected at validation"),
    "timed_out": ("server", "query deadline expired before dispatch"),
    "launch_failure": ("executor", "device launch raised; batch failed"),
}

# name -> (type, unit, description); type in {counter, gauge, histogram}
INSTRUMENTS = {
    "queries_submitted": ("counter", "1", "queries accepted at admission"),
    "queries_resolved": ("counter", "1", "query futures resolved (any "
                                         "status)"),
    "mutations_applied": ("counter", "1", "mutation batches applied"),
    "wal_appends": ("counter", "1", "WAL records appended"),
    "snapshots_written": ("counter", "1", "snapshots written"),
    "faults_detected": ("counter", "1", "checkpoint-runner detections"),
    "rollbacks": ("counter", "1", "checkpoint-runner rollbacks"),
    "spans_dropped": ("counter", "1", "spans lost to the ring bound"),
    "epoch": ("gauge", "1", "current snapshot epoch"),
    "wal_records": ("gauge", "1", "records in the live WAL"),
    "query_latency_ms": ("histogram", "ms", "submit -> resolve latency"),
    "batch_fill": ("histogram", "1", "queries per dispatched batch"),
    "device_ms": ("histogram", "ms", "device launch wall-time"),
    "wal_append_ms": ("histogram", "ms", "WAL append wall-time"),
}

# device scope -> (module, description); nested scopes join with "/"
DEVICE_SCOPES = {
    "superstep.init": ("core/superstep.py",
                       "prepare + init: the state before round one"),
    "superstep.loop": ("core/superstep.py",
                       "the round loop: its control and the carry "
                       "copies XLA adds"),
    "superstep.step": ("core/superstep.py",
                       "one round of the program, and the round count"),
    "superstep.halt": ("core/superstep.py",
                       "the loop condition: halt test and round cap"),
    "superstep.guard": ("core/superstep.py",
                        "per-round invariant check (guard=True builds)"),
    "superstep.telemetry": ("core/superstep.py",
                            "per-round series row (telemetry=True "
                            "builds)"),
    "superstep.outputs": ("core/superstep.py",
                          "final outputs from the halted state"),
    "bfs.push": ("core/bfs.py",
                 "bfs/fast push level: scatter-OR over out-edges"),
    "bfs.pull": ("core/bfs.py",
                 "bfs/fast pull level: pull over in-edges"),
    "bfs.derive_parents": ("core/bfs.py",
                           "min-id in-frontier parent of each new "
                           "vertex"),
    "pagerank.compressed": ("core/pagerank.py",
                            "pagerank/fast step shipping bf16 with "
                            "error feedback"),
    "pagerank.exact": ("core/pagerank.py",
                       "pagerank/fast step shipping f32"),
    "localops.spmv_pull": ("core/localops.py",
                           "sum of x over in-neighbours (SpMV)"),
    "localops.frontier_pull": ("core/localops.py",
                               "min-id in-neighbour in the frontier"),
    "localops.pull_min_eq": ("core/localops.py",
                             "min-id in-neighbour with a matching "
                             "value"),
    "localops.scatter_combine": ("core/localops.py",
                                 "combine per-edge values per row"),
    "localops.push_combine": ("core/localops.py",
                              "combine a source-vertex field per "
                              "destination (ell_in at parts=1, ell_dst "
                              "at parts>1)"),
    "<ell>.b<i>": ("core/localops.py",
                   "gather and row reduction of bucket i of an ELL "
                   "structure"),
    "reorder": ("core/localops.py",
                "bucket rows back to row order (inverse permutation)"),
    "exchange.sum": ("core/partitioned.py", "reduce-scatter SUM"),
    "exchange.or": ("core/partitioned.py",
                    "bit-packed all-to-all OR"),
    "exchange.min": ("core/partitioned.py", "all-to-all MIN"),
    "exchange.bcast": ("core/partitioned.py",
                       "all-gather of a vertex field"),
    "exchange.psum": ("core/partitioned.py",
                      "all-reduce of a scalar (halt counts)"),
    "exchange.<op>_start": ("core/partitioned.py",
                            "issue a double-buffered exchange"),
    "exchange.<op>_finish": ("core/partitioned.py",
                             "reduce a double-buffered exchange's "
                             "handle"),
}

# host span (profiler name repro.<name>) -> (module, description)
PROGRAM_SPANS = {
    "graph.partition": ("core/graph.py",
                        "partition_graph: the whole host build"),
    "graph.coo": ("core/graph.py",
                  "out- and in-edge COO shards (_group_edges)"),
    "graph.ell": ("core/graph.py",
                  "the four blocked-ELL builds, side by side"),
    "graph.ell.<ell>": ("core/graph.py",
                        "one blocked-ELL build, in its worker thread"),
    "engine.upload": ("core/api.py",
                      "device_graph: host arrays to the mesh"),
    "engine.lower": ("core/api.py",
                     "CompiledProgram.lower: trace and lower"),
    "engine.call": ("core/api.py",
                    "CompiledProgram.__call__: host dispatch of a "
                    "launch"),
}


def _pattern(name: str) -> str:
    return "".join(r"[A-Za-z0-9_]+" if part.startswith("<")
                   else re.escape(part)
                   for part in re.split(r"(<[a-z]+>)", name))


_DECLARED = {where: re.compile("|".join(_pattern(k) for k in table))
             for where, table in (("device", DEVICE_SCOPES),
                                  ("host", PROGRAM_SPANS))}


@functools.lru_cache(maxsize=None)
def declared(name: str, where: str = "device") -> bool:
    """Whether ``name`` is a declared device scope (``where="device"``)
    or program span (``"host"``), placeholders matching one
    identifier."""
    return _DECLARED[where].fullmatch(name) is not None


class Registry:
    """Runtime counters/gauges/histograms over the declared names."""

    def __init__(self):
        self._counters: dict[str, int] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, list[float]] = {}

    @staticmethod
    def _check(name: str, typ: str) -> None:
        decl = INSTRUMENTS.get(name)
        if decl is None or decl[0] != typ:
            raise KeyError(f"{name!r} is not a declared {typ} "
                           "(add it to obs.registry.INSTRUMENTS)")

    def count(self, name: str, n: int = 1) -> None:
        self._check(name, "counter")
        self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        self._check(name, "gauge")
        self._gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        self._check(name, "histogram")
        self._hists.setdefault(name, []).append(float(value))

    def snapshot(self) -> dict:
        """JSON-friendly state; histograms roll up to count/mean/p99."""
        hists = {}
        for name, vals in sorted(self._hists.items()):
            arr = np.asarray(vals, dtype=np.float64)
            hists[name] = {
                "count": int(arr.size),
                "mean": float(arr.mean()) if arr.size else 0.0,
                "p99": float(np.percentile(arr, 99)) if arr.size else 0.0,
            }
        return {"counters": dict(sorted(self._counters.items())),
                "gauges": dict(sorted(self._gauges.items())),
                "histograms": hists}


def spans_markdown_table() -> str:
    """Span + event kinds as one markdown table (docs-drift-tested)."""
    lines = ["| kind | component | shape | description |",
             "|------|-----------|-------|-------------|"]
    for kind, (comp, export, desc) in SPAN_KINDS.items():
        lines.append(f"| `{kind}` | {comp} | span ({export}) | {desc} |")
    for kind, (comp, desc) in EVENT_KINDS.items():
        lines.append(f"| `{kind}` | {comp} | event | {desc} |")
    return "\n".join(lines)


def instruments_markdown_table() -> str:
    """The instrument registry as a markdown table."""
    lines = ["| instrument | type | unit | description |",
             "|------------|------|------|-------------|"]
    for name, (typ, unit, desc) in INSTRUMENTS.items():
        lines.append(f"| `{name}` | {typ} | {unit} | {desc} |")
    return "\n".join(lines)


def scopes_markdown_table() -> str:
    """Device scopes and program spans as one markdown table."""
    lines = ["| name | where | module | description |",
             "|------|-------|--------|-------------|"]
    for where, table in (("device scope", DEVICE_SCOPES),
                         ("host span", PROGRAM_SPANS)):
        for name, (module, desc) in table.items():
            lines.append(f"| `{name}` | {where} | `{module}` | {desc} |")
    return "\n".join(lines)
