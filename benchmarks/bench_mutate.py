"""Dynamic-graph benchmark: in-place mutation throughput and the
incremental-recompute win on the resident server; writes
``BENCH_mutate.json`` at the repo root.

  PYTHONPATH=src python -m benchmarks.bench_mutate [--fast]

One subprocess (so ``XLA_FLAGS=--xla_force_host_platform_device_count``
binds the partition count before jax imports) builds a server, serves a
PageRank refresh at epoch 0, applies a K-edge delete batch then a
K-edge insert batch through ``GraphServer.mutate`` (both must take the
in-place slot-patch path — a rebuild fails the run), and re-serves
PageRank both ways on the mutated graph:

  * ``mutate/apply``    — batched patch wall time; the summary reports
    edges/sec applied and asserts ``rebuild`` never fired;
  * ``pagerank/warm``   — warm restart from the epoch-0 served rank;
  * ``pagerank/cold``   — the cold uniform start, same tolerance.

The summary records ``rounds_warm``/``rounds_cold`` and their ratio,
plus the warm program's wire MB per part from its AOT collectives
(``repro.roofline.analysis.parse_collectives``).  The run FAILS (exit
3) unless the warm restart converges in strictly fewer rounds than
cold — the dynamic-subsystem acceptance floor.  ``benchmarks/
compare.py`` gates the committed rows per (algo, variant) cell with
the same threshold/jitter-floor/cross-config rules as BENCH_graph.json.
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

_CELL_CODE = r"""
import json, time
import numpy as np
from repro.configs import graph_workloads
from repro.core import GraphEngine, localops, partition_graph
from repro.core.runtime import runtime_fingerprint
from repro.graphs import generate_edges
from repro.launch.mesh import make_graph_mesh
from repro.roofline import analysis as RA
from repro.serve import GraphServer, query

graph, parts, k_edges = {graph!r}, {parts}, {k_edges}
PR = dict(iters=300, tol=1e-6)          # identical tolerance both ways
gcfg = graph_workloads.ALL[graph]
edges = generate_edges(gcfg, seed=42)
g = partition_graph(edges, gcfg.num_vertices, parts)
eng = GraphEngine(g, make_graph_mesh(parts))
server = GraphServer(eng, buckets=(1,))
server.warmup([query("pagerank", **PR).key,
               query("pagerank", "warm", **PR).key])
print("META " + json.dumps({{
    "localops": localops.get_mode(), **runtime_fingerprint()}}))

# epoch 0: the refresh whose served rank becomes the warm seed
server.serve([query("pagerank", **PR)])

# delete K live edges, then insert K fresh ones (the freed slots
# guarantee insert capacity, so neither batch may fall back to rebuild)
dyn = server.dynamic_graph()
rng = np.random.default_rng(7)
s_del = server.mutate(deletes=dyn.sample_deletable(k_edges, rng))
s_ins = server.mutate(inserts=dyn.sample_insertable(k_edges, rng))
assert not (s_del.rebuild or s_ins.rebuild), "mutation fell back to rebuild"
apply_s = s_del.apply_s + s_ins.apply_s
print("RESULT " + json.dumps({{
    "algo": "mutate", "variant": "apply", "graph": graph, "parts": parts,
    "ms": apply_s * 1e3, "edges": 2 * k_edges,
    "edges_per_s": 2 * k_edges / apply_s,
    "slots_patched": s_del.slots_patched + s_ins.slots_patched}}))

# epoch 2: recompute on the mutated graph, warm then cold.  The warm
# query must run FIRST - serving it updates the stored seed, so a
# second warm launch would trivially converge in one round.
for variant, label in ((("pagerank", "warm"), "warm"),
                       (("pagerank",), "cold")):
    (res,) = server.serve([query(*variant, **PR)])
    print("RESULT " + json.dumps({{
        "algo": "pagerank", "variant": label, "graph": graph,
        "parts": parts, "ms": res.latency_s * 1e3,
        "rounds": int(res.rounds), "epoch": res.epoch}}))

stats = RA.parse_collectives(
    eng.program("pagerank", "warm", **PR).aot().as_text())
print("WIRE " + json.dumps(
    {{"wire_mb_per_part": stats.total_wire_bytes / parts / 1e6}}))
"""


def run_cells(graph: str, parts: int, k_edges: int):
    code = _CELL_CODE.format(graph=graph, parts=parts, k_edges=k_edges)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={parts} "
                        + env.get("XLA_FLAGS", "")).strip()
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"mutate bench subprocess failed:\n{proc.stdout[-2000:]}\n"
            f"{proc.stderr[-4000:]}")
    rows, meta, wire = [], {}, {}
    for line in proc.stdout.splitlines():
        if line.startswith("META "):
            meta = json.loads(line[len("META "):])
        elif line.startswith("RESULT "):
            rows.append(json.loads(line[len("RESULT "):]))
        elif line.startswith("WIRE "):
            wire = json.loads(line[len("WIRE "):])
    return rows, meta, wire


def summary_section(rows: list[dict], wire: dict) -> dict:
    by = {(r["algo"], r["variant"]): r for r in rows}
    apply_row = by[("mutate", "apply")]
    warm, cold = by[("pagerank", "warm")], by[("pagerank", "cold")]
    return {
        "edges_applied": apply_row["edges"],
        "edges_per_s": round(apply_row["edges_per_s"], 1),
        "rounds_warm": warm["rounds"], "rounds_cold": cold["rounds"],
        "speedup_rounds": round(cold["rounds"] / max(warm["rounds"], 1), 2),
        "wire_mb_per_part": round(wire.get("wire_mb_per_part", 0.0), 3),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller graph / smaller batches (CI mode)")
    ap.add_argument("--graph", default=None,
                    help="override the suite's graph config")
    ap.add_argument("--parts", type=int, default=2)
    ap.add_argument("--edges", type=int, default=None,
                    help="edges per mutation batch (delete and insert)")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_mutate.json"))
    args = ap.parse_args(argv)

    graph = args.graph or ("urand12" if args.fast else "urand16")
    k_edges = args.edges or (256 if args.fast else 1024)

    print(f"[bench_mutate] {graph} parts={args.parts} "
          f"batch={k_edges} edges (delete + insert)")
    rows, sub_meta, wire = run_cells(graph, args.parts, k_edges)
    for r in rows:
        extra = (f"{r['edges_per_s']:10.0f} edges/s"
                 if r["algo"] == "mutate" else f"{r['rounds']:6d} rounds")
        print(f"[bench_mutate] {r['algo'] + '/' + r['variant']:16s} "
              f"{r['ms']:9.1f} ms  {extra}")

    summary = summary_section(rows, wire)
    print(f"[bench_mutate] warm restart: {summary['rounds_warm']} rounds "
          f"vs cold {summary['rounds_cold']} "
          f"({summary['speedup_rounds']:.2f}x fewer); "
          f"wire {summary['wire_mb_per_part']:.3f} MB/part")

    meta = {"graph": graph, "parts": args.parts, "launches": k_edges,
            "mode": "fast" if args.fast else "full", "layout": "ell",
            "localops": sub_meta.get(
                "localops", os.environ.get("REPRO_LOCALOPS", "auto")),
            "jax": sub_meta.get("jax"), "device": sub_meta.get("device")}
    payload = {"meta": meta, "rows": rows, "summary": summary}
    pathlib.Path(args.out).write_text(
        json.dumps(payload, indent=2) + "\n")
    print(f"[bench_mutate] wrote {args.out} ({len(rows)} rows)")
    if summary["rounds_warm"] >= summary["rounds_cold"]:
        print(f"[bench_mutate] FAIL: warm restart took "
              f"{summary['rounds_warm']} rounds, not fewer than cold's "
              f"{summary['rounds_cold']}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
