"""Serve-path benchmark: closed-loop (program x bucket) cells through
the resident-engine GraphServer; writes ``BENCH_serve.json`` at the
repo root.

  PYTHONPATH=src python -m benchmarks.bench_serve [--fast]

Each cell floods one server (ladder pinned to a single bucket) with
``launches x bucket`` source queries and records queries/sec and
p50/p95/p99 admission-to-demux latency.  The ``bucket=1`` cell IS the
one-query-per-launch baseline, so ``qps(bucket=B) / qps(bucket=1)``
measures the coalescing win directly — the fast suite asserts the
batched-BFS ratio (recorded in the artifact's ``speedup`` section)
stays >= 3x.  Refresh programs (``cc``) bench as sequential shared
launches (``bucket=0``).

A final ``bucket="overload"`` row replays a bfs trace at 2x the
measured closed-loop capacity through a bounded-queue, deadlined
server: it records admitted qps / p99 plus ``shed`` and ``timed_out``
counts, and the subprocess asserts in-line that p99 of admitted
answers holds the deadline and that ok answers under overload stay
bit-identical to direct ``program()`` calls.

A ``bucket="recovery"`` row times restart recovery: a durable server
(WAL + snapshots, ``repro.serve.persist``) runs a short mutation trace,
is abandoned, and ``GraphServer.recover()`` rebuilds it from the
directory — the row records ``ttfok_ms`` (recover start to first ok
answer), epochs replayed from the WAL, and the snapshot epoch resumed
from, with in-line asserts that the recovered server lands on the exact
killed epoch and serves bit-identical bfs parents.

Like ``benchmarks/graph_scaling.py``, the measurement runs in ONE
subprocess so ``XLA_FLAGS=--xla_force_host_platform_device_count`` can
force the partition count before jax imports; the harness process never
imports jax.  ``benchmarks/compare.py`` gates the committed rows per
(algo, bucket) cell with the same threshold/jitter-floor/cross-config
rules as BENCH_graph.json.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
SRC = str(REPO_ROOT / "src")

# (algo, bucket) cells; bucket 0 = sequential shared refresh launches.
# 3 rooted algorithms x >= 2 bucket sizes + the bucket=1 baselines.
FAST_CELLS = [
    ("bfs", (1, 8, 32)),
    ("sssp", (1, 8, 32)),
    ("betweenness", (1, 8)),
    ("cc", (0,)),
]
FULL_CELLS = [
    ("bfs", (1, 8, 32, 128)),
    ("sssp", (1, 8, 32, 128)),
    ("betweenness", (1, 8, 32)),
    ("cc", (0,)),
    ("pagerank", (0,)),
]

_CELL_CODE = r"""
import json
from repro.configs import graph_workloads
from repro.core import GraphEngine, localops, partition_graph
from repro.core.runtime import runtime_fingerprint
from repro.graphs import generate_edges
from repro.launch.mesh import make_graph_mesh
from repro.serve import GraphServer, Query, make_key

graph, parts, cells, launches = {graph!r}, {parts}, {cells!r}, {launches}
gcfg = graph_workloads.ALL[graph]
edges = generate_edges(gcfg, seed=42)
g = partition_graph(edges, gcfg.num_vertices, parts)
eng = GraphEngine(g, make_graph_mesh(parts))
print("META " + json.dumps({{
    "localops": localops.get_mode(), **runtime_fingerprint()}}))
rows_all = []
for algo, bucket in cells:
    key = make_key(algo)
    server = GraphServer(eng, buckets=(max(bucket, 1),))
    server.warmup([key])
    # small buckets run MORE launches so every cell carries similar
    # measurement mass (the bucket=1 baseline would otherwise be a
    # handful of ms of wall time - pure scheduler jitter)
    n_launch = launches if bucket == 0 else max(launches, 32 // bucket)
    if bucket == 0:
        for _ in range(n_launch):           # sequential shared refreshes
            server.serve([Query(key, None)])
    else:
        roots = [(7 * i) % gcfg.num_vertices
                 for i in range(n_launch * bucket)]
        server.serve([Query(key, r) for r in roots])
    (row,) = server.metrics.rows()
    rows_all.append(row)
    print("RESULT " + json.dumps(row))

# -- overload cell: a 2x-capacity bfs trace through a bounded-queue,
# deadlined server.  Offered rate = 2x the measured closed-loop qps of
# the same bucket, so the cell tracks "how gracefully does the server
# degrade": p99 of ADMITTED answers must hold the deadline (lapsed ones
# resolve timed_out, never recorded), the bounded queue sheds the rest,
# and every ok answer stays bit-identical to a direct program() call.
import numpy as np
import jax.numpy as jnp
from repro.serve import synthetic_trace

ob, deadline = {overload_bucket}, {deadline_s}
cap_qps = max(r["qps"] for r in rows_all
              if r["algo"].startswith("bfs") and r["bucket"] == ob)
server = GraphServer(eng, buckets=(ob,), max_queued=4 * ob,
                     default_deadline_s=deadline)
server.warmup([make_key("bfs")])
trace = synthetic_trace(gcfg.num_vertices, "bfs", rate=2.0 * cap_qps,
                        duration={overload_duration}, seed=99)
res = server.serve_trace(trace)
by_qid = {{q.qid: q for _, q in trace}}
garr, prog, checked = eng.device_graph(), eng.program("bfs"), 0
for r in res:
    if r.ok and checked < 8:
        p, _ = prog(garr, jnp.int32(by_qid[r.qid].root))
        assert (np.asarray(r["parents"])
                == eng.gather_vertex_field(p)).all(), \
            "overload ok answer differs from direct program() call"
        checked += 1
assert checked > 0, "overload trace produced no ok answers"
(orow,) = server.metrics.rows()
assert orow["p99_ms"] <= deadline * 1e3, \
    "p99 of admitted answers exceeds the deadline"
orow = dict(orow, bucket="overload",
            offered_qps=round(2.0 * cap_qps, 1),
            shed=server.metrics.counts["shed"],
            timed_out=server.metrics.counts["timed_out"],
            deadline_s=deadline)
print("RESULT " + json.dumps(orow))

# -- recovery cell: a durable server runs a short mutation trace, is
# abandoned mid-flight (the live object stands in for a killed
# process - the on-disk WAL/snapshot state is identical either way),
# and GraphServer.recover() restarts from the directory.  ttfok =
# recover() start to the first ok answer off the recovered server,
# asserted in-line to land on the exact killed epoch with the bfs
# parents bit-identical to the pre-kill server's.
import tempfile, time
from repro.serve import Persistence

pdir = tempfile.mkdtemp(prefix="bench-recovery-")
dserver = GraphServer(eng, buckets=(8,), persistence=Persistence(
    dir=pdir, snapshot_every=4, fsync=False))
dserver.warmup([make_key("bfs")])
rng = np.random.default_rng(7)
for _ in range(3):
    dserver.mutate(deletes=dserver.dynamic_graph()
                   .sample_deletable(16, rng))
    dserver.mutate(inserts=dserver.dynamic_graph()
                   .sample_insertable(16, rng))
    live = dserver.serve([Query(make_key("bfs"), 3)])
killed_epoch = dserver.epoch
t0 = time.perf_counter()
rec = GraphServer.recover(pdir, buckets=(8,))
res = rec.serve([Query(make_key("bfs"), 3)])
ttfok = time.perf_counter() - t0
assert rec.epoch == killed_epoch and res[0].ok, \
    (rec.epoch, killed_epoch, res[0].status)
assert (np.asarray(res[0]["parents"])
        == np.asarray(live[0]["parents"])).all(), \
    "recovered answer differs from the pre-kill server's"
rep = rec.recovery_report
ms = round(ttfok * 1e3, 1)
print("RESULT " + json.dumps({{
    "algo": "bfs_fast", "bucket": "recovery", "count": 1,
    "qps": round(1.0 / ttfok, 3),
    "p50_ms": ms, "p95_ms": ms, "p99_ms": ms, "ttfok_ms": ms,
    "epochs_replayed": rep.replayed, "wal_records": rep.wal_records,
    "snapshot_epoch": rep.snapshot_epoch}}))

# -- obs session: a SHORT traced replay on its OWN server, so every
# gated cell above ran un-instrumented (tracing on the timed path
# would be a confound).  Its span summary rides the artifact as
# informational context; compare.py never gates on it.
from repro.obs import SpanRecorder, trace_summary
orec = SpanRecorder()
oserver = GraphServer(eng, buckets=(8,), obs=orec)
oserver.warmup([make_key("bfs")])
oserver.serve([Query(make_key("bfs"), (13 * i) % gcfg.num_vertices)
               for i in range(16)])
print("TRACE " + json.dumps(trace_summary(orec)))
"""


def run_cells(graph: str, parts: int, cells, launches: int,
              overload_duration: float = 0.5):
    flat = [(a, b) for a, bs in cells for b in bs]
    code = _CELL_CODE.format(graph=graph, parts=parts, cells=flat,
                             launches=launches, overload_bucket=8,
                             deadline_s=0.25,
                             overload_duration=overload_duration)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={parts} "
                        + env.get("XLA_FLAGS", "")).strip()
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"serve bench subprocess failed:\n{proc.stdout[-2000:]}\n"
            f"{proc.stderr[-4000:]}")
    rows, meta, trace_sum = [], {}, None
    for line in proc.stdout.splitlines():
        if line.startswith("META "):
            meta = json.loads(line[len("META "):])
        elif line.startswith("RESULT "):
            rows.append(json.loads(line[len("RESULT "):]))
        elif line.startswith("TRACE "):
            trace_sum = json.loads(line[len("TRACE "):])
    return rows, meta, trace_sum


def speedup_section(rows: list[dict], algo_label: str = "bfs_fast") -> dict:
    """Coalesced-vs-single throughput for one program's ladder."""
    cells = {r["bucket"]: r["qps"] for r in rows
             if r["algo"] == algo_label and isinstance(r["bucket"], int)}
    if 1 not in cells or len(cells) < 2:
        return {}
    top = max(b for b in cells if b != 1)
    return {"algo": algo_label, "bucket": top,
            "single_qps": cells[1], "coalesced_qps": cells[top],
            "speedup": round(cells[top] / max(cells[1], 1e-9), 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller graph / fewer launches (CI mode)")
    ap.add_argument("--graph", default=None,
                    help="override the suite's graph config")
    ap.add_argument("--parts", type=int, default=2)
    ap.add_argument("--launches", type=int, default=None,
                    help="coalesced launches per cell")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_serve.json"))
    ap.add_argument("--speedup-floor", type=float, default=3.0,
                    help="exit non-zero when the coalesced-vs-single "
                         "bfs qps ratio falls below this (the PR-5 "
                         "acceptance floor; 0 disables)")
    args = ap.parse_args(argv)

    graph = args.graph or ("urand12" if args.fast else "urand16")
    launches = args.launches or (3 if args.fast else 6)
    cells = FAST_CELLS if args.fast else FULL_CELLS

    print(f"[bench_serve] {graph} parts={args.parts} "
          f"launches/cell={launches} "
          f"cells={[(a, list(b)) for a, b in cells]}")
    rows, sub_meta, trace_sum = run_cells(
        graph, args.parts, cells, launches,
        overload_duration=0.5 if args.fast else 1.0)
    for r in rows:
        b = str(r["bucket"]) if r["bucket"] else "shared"
        if r["bucket"] == "overload":
            extra = (f" shed={r['shed']} timed_out={r['timed_out']} "
                     f"offered={r['offered_qps']:.0f}q/s")
        elif r["bucket"] == "recovery":
            extra = (f" ttfok={r['ttfok_ms']:.0f}ms "
                     f"replayed={r['epochs_replayed']} "
                     f"snapshot_epoch={r['snapshot_epoch']}")
        else:
            extra = ""
        print(f"[bench_serve] {r['algo']:16s} bucket={b:>8s} "
              f"qps={r['qps']:8.1f} p50={r['p50_ms']:7.1f}ms "
              f"p99={r['p99_ms']:7.1f}ms" + extra)

    speedup = speedup_section(rows)
    below_floor = (speedup and args.speedup_floor
                   and speedup["speedup"] < args.speedup_floor)
    if speedup:
        print(f"[bench_serve] coalescing win ({speedup['algo']} bucket "
              f"{speedup['bucket']} vs 1): {speedup['speedup']:.1f}x "
              f"({speedup['coalesced_qps']:.1f} vs "
              f"{speedup['single_qps']:.1f} q/s)"
              + (f"  <-- BELOW the {args.speedup_floor:.0f}x acceptance "
                 "floor" if below_floor else ""))

    meta = {"graph": graph, "parts": args.parts, "launches": launches,
            "mode": "fast" if args.fast else "full", "layout": "ell",
            "localops": sub_meta.get(
                "localops", os.environ.get("REPRO_LOCALOPS", "auto")),
            "jax": sub_meta.get("jax"), "device": sub_meta.get("device")}
    payload = {"meta": meta, "rows": rows, "speedup": speedup}
    if trace_sum is not None:
        # span summary of the short traced session (separate server —
        # the gated cells ran un-instrumented); informational only,
        # compare.py ignores it
        payload["trace_summary"] = trace_sum
        print(f"[bench_serve] obs session: {trace_sum['spans_total']} "
              f"spans, top p99: "
              + ", ".join(f"{r['kind']}={r['p99_ms']:.2f}ms"
                          for r in trace_sum["top_p99_ms"]))
    pathlib.Path(args.out).write_text(
        json.dumps(payload, indent=2) + "\n")
    print(f"[bench_serve] wrote {args.out} ({len(rows)} rows)")
    if below_floor:
        print(f"[bench_serve] FAIL: coalescing speedup "
              f"{speedup['speedup']:.2f}x < floor "
              f"{args.speedup_floor:.1f}x", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
