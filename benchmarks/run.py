"""Benchmark harness: one entry per paper table/figure + roofline table.

  PYTHONPATH=src python -m benchmarks.run [--fast]

Outputs CSV-ish lines per benchmark, writes JSON artifacts under
artifacts/, and writes a machine-readable ``BENCH_graph.json`` at the
repo root (one row per algorithm x variant x partition count with the
measured ms) so the perf trajectory is tracked across PRs.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def runtime_meta() -> dict:
    """jax version + device kind, read in a SUBPROCESS — the harness
    itself never imports jax (each bench point is a subprocess that
    must set XLA_FLAGS before its first jax import).  Recorded in the
    bench meta so benchmarks/compare.py can tell environment drift
    (jax upgrade, CPU-vs-TPU move) from real regressions."""
    code = ("import json; from repro.core.runtime import "
            "runtime_fingerprint; print(json.dumps(runtime_fingerprint()))")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    try:
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception:  # noqa: BLE001 - meta is best-effort
        return {"jax": None, "device": None}


def write_bench_artifact(rows: list[dict], meta: dict,
                         path=None) -> pathlib.Path:
    """Write BENCH_graph.json: {meta, rows: [{algo, variant, graph,
    parts, ms, wire_mb, rounds_to_converge}]}.  ``meta`` records
    graphs/reps/mode — and each row carries its own graph — so cross-PR
    comparisons never silently mix measurement configurations.
    ``rounds_to_converge`` is the driver's actual round count (early
    exit for convergent programs, the fixed budget for iteration-capped
    ones): deterministic per configuration, so compare.py gates it
    exactly — an async variant silently paying extra rounds is an
    algorithmic regression wall-time jitter could hide."""
    out = path or (REPO_ROOT / "BENCH_graph.json")
    slim = [{
        "algo": r["algo"],
        "variant": r["mode"],
        "graph": r["graph"],
        "parts": r["parts"],
        "ms": round(r["ms"], 2),
        "wire_mb_per_part": round(r["wire_bytes_per_part"] / 1e6, 3),
        "rounds_to_converge": r["rounds"],
        # per-row engine-telemetry summary (per-round probe series +
        # tap-level wire bytes) — INFORMATIONAL ONLY: compare.py never
        # gates on it and tolerates rows without it (older baselines)
        **({"telemetry": r["telemetry"]} if "telemetry" in r else {}),
    } for r in rows]
    pathlib.Path(out).write_text(
        json.dumps({"meta": meta, "rows": slim}, indent=2) + "\n")
    print(f"[bench] wrote {out} ({len(slim)} rows)")
    return pathlib.Path(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller graphs / fewer reps (CI mode)")
    ap.add_argument("--skip-scaling", action="store_true",
                    help="skip the multi-process scaling figures")
    args = ap.parse_args()

    graph = "urand16"
    parts = (1, 2) if args.fast else (1, 2, 4, 8)
    reps = 2 if args.fast else 3

    graph_rows: list[dict] = []

    print("=" * 72)
    print("Figure 1: distributed BFS, BSP(Boost-like) vs HPX-adapted")
    print("=" * 72)
    if not args.skip_scaling:
        from benchmarks.bench_bfs import main as bfs_main
        graph_rows += bfs_main(graph=graph, parts=parts, reps=reps)

    print("=" * 72)
    print("Figure 2: distributed PageRank, BSP(Boost-like) vs HPX-adapted")
    print("=" * 72)
    if not args.skip_scaling:
        from benchmarks.bench_pagerank import main as pr_main
        graph_rows += pr_main(graph=graph, parts=parts, reps=reps)

    # the registry's post-paper programs (ROADMAP: "full NWGraph set").
    # Benchmarked on urand12: triangle counting's rotation exchange is
    # O(n^2/P) memory/compute, so its bench point is a graph inside its
    # n_budget; kcore/betweenness ride the same graph for comparability.
    graph_extra = "urand12"
    print("=" * 72)
    print(f"New algorithms: triangles / kcore / betweenness ({graph_extra})")
    print("=" * 72)
    if not args.skip_scaling:
        from benchmarks.graph_scaling import scaling_table
        for algo in ("triangles", "kcore", "betweenness"):
            graph_rows += scaling_table(graph_extra, algo,
                                        parts_list=parts, reps=reps)

    if graph_rows:
        # the localops mode/layout steer which hot-loop implementation
        # was measured; recorded so cross-PR comparisons (compare.py)
        # never silently mix dispatch configurations.  Read from the env
        # (not repro.core.localops): each bench point is a subprocess
        # inheriting this env, and the harness never imports jax.
        write_bench_artifact(graph_rows, {
            "graph": graph, "graph_new_algos": graph_extra,
            "parts": list(parts), "reps": reps,
            "mode": "fast" if args.fast else "full",
            "localops": os.environ.get("REPRO_LOCALOPS", "auto"),
            "layout": "ell", **runtime_meta()})

    print("=" * 72)
    print("Kernel micro-benchmarks (CPU oracle time + TPU roofline bound)")
    print("=" * 72)
    from benchmarks.bench_kernels import main as k_main
    k_main()

    print("=" * 72)
    print("Roofline table (from dry-run artifacts; see EXPERIMENTS.md)")
    print("=" * 72)
    try:
        from benchmarks.roofline_table import main as r_main
        r_main()
    except Exception as e:  # noqa: BLE001 - artifacts may not exist yet
        print(f"(roofline table unavailable: {e!r}; "
              "run python -m repro.launch.dryrun first)")


if __name__ == "__main__":
    main()
