"""Graph-analytics driver: the paper's workload end to end.

Generates a urand/rmat/smallworld graph, partitions it over the
available devices, runs EVERY algorithm program in the registry (BFS +
PageRank in both BSP-baseline and HPX-adapted modes, SSSP, CC, triangle
counting, k-core, betweenness), verifies results, and reports timings.
Programs whose ``n_budget`` the graph exceeds (the O(n^2/P)
triangle-counting bitmap) are skipped with a note.  ``--multi-source B``
additionally runs the batched multi-source traversal programs (B roots
per launch) and reports per-query amortized time — the
serve-many-queries scenario.  ``--layout coo`` is the escape hatch back
to the COO scatter reference path (the default ``ell`` routes every
hot loop through the blocked-ELL local ops in ``core/localops.py``).
``--obs`` re-runs each program with engine telemetry on (per-round
halt/probe series + wire bytes per exchange primitive, ``repro.obs``)
and ``--profile-dir DIR`` runs the programs under a ``jax.profiler``
trace written to DIR: it names the device work by scope
(``superstep.step``, ``localops.spmv_pull``, ...; ``obs/scopes.py``)
and the host phases as ``repro.*`` spans.

  PYTHONPATH=src python -m repro.launch.graph_analytics --graph urand18
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.graph_analytics \
      --graph urand20 --parts 8 --multi-source 16
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import graph_workloads
from repro.core import GraphEngine, incremental, partition_graph, registry
from repro.core.registry import program_label
from repro.core.runtime import enable_compile_cache
from repro.graphs import generate_edges
from repro.launch.mesh import make_graph_mesh

def _timed(fn, args):
    out = fn(*args)               # compile
    jax.block_until_ready(out)
    t0 = time.time()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.time() - t0


def run(graph_name: str, parts: int, *, pr_iters: int = 50,
        verify: bool = True, seed: int = 42, multi_source: int = 0,
        layout: str = "ell", exec_mode: str = "all", obs: bool = False,
        profile_dir: str | None = None):
    from repro.core import localops
    gcfg = graph_workloads.ALL[graph_name]
    print(f"[graph] generating {graph_name}: 2^{gcfg.scale} vertices, "
          f"{gcfg.num_edges:,} edges ({gcfg.generator})")
    edges = generate_edges(gcfg, seed)
    t0 = time.time()
    g = partition_graph(edges, gcfg.num_vertices, parts)
    ell_slots = sum(m.slots for m in g.ell_meta.values())
    print(f"[graph] partitioned over {parts} parts in {time.time()-t0:.1f}s "
          f"(n_local={g.n_local:,}, e_max={g.e_max:,}; layout={layout} "
          f"ell_slots/part={ell_slots:,} localops={localops.get_mode()})")
    eng = GraphEngine(g, make_graph_mesh(parts), layout=layout)
    garr = eng.device_graph()
    root = jnp.int32(0)
    results = {}
    if profile_dir:
        jax.profiler.start_trace(profile_dir)

    for algo, variant in registry.available():
        spec = registry.get_spec(algo, variant)
        name = program_label(algo, variant)
        if exec_mode != "all" and spec.exec_mode != exec_mode:
            continue
        if spec.n_budget and g.n > spec.n_budget:
            print(f"[graph] {name:14s}   skipped (n={g.n:,} exceeds its "
                  f"n_budget={spec.n_budget:,})")
            continue
        params = {"iters": pr_iters} if algo == "pagerank" else {}
        prog = eng.program(algo, variant, **params)
        if any(k != "scalar" for k in spec.input_kinds):
            # seeded incremental variants run from their cold seed here
            # (the warm path needs a previous epoch — that's the server)
            (seed_arr,) = incremental.cold_seed(spec, g)
            args = (garr, eng.scatter_vertex_field(
                seed_arr, incremental.KIND_DTYPES[spec.input_kinds[0]]))
        else:
            args = (garr,) + (root,) * len(spec.inputs)
        out, dt = _timed(prog, args)
        results[name] = (out, dt)
        print(f"[graph] {name:14s} {dt*1e3:9.1f} ms")
        if obs:
            # a SEPARATE telemetry build (telemetry is a compile-cache
            # dimension), run after the timed one so the headline ms
            # stays the un-instrumented number
            tprog = eng.program(algo, variant, telemetry=True, **params)
            tout = tprog(*args)
            s = tprog.run_telemetry(tout[-1]).summary()
            wire = s.get("wire_bytes_per_round", {})
            print(f"[obs]   {name:14s} rounds={s['rounds']:3d} "
                  f"wall={s.get('wall_ms', 0.0):8.1f} ms  wire/round="
                  + (" ".join(f"{op}:{b:,}B"
                              for op, b in wire.items()) or "none"))

    if multi_source:
        roots = jnp.arange(multi_source, dtype=jnp.int32)
        for algo, variant in registry.available():
            spec = registry.get_spec(algo, variant)
            if (not spec.inputs or variant == "bsp"
                    or any(k != "scalar" for k in spec.input_kinds)):
                continue          # batch only the rooted traversal fast paths
            if exec_mode != "all" and spec.exec_mode != exec_mode:
                continue
            if spec.n_budget and g.n > spec.n_budget:
                continue
            prog = eng.program(algo, variant, batch=multi_source)
            name = f"{program_label(algo, variant)}_x{multi_source}"
            out, dt = _timed(prog, (garr, roots))
            results[name] = (out, dt)
            print(f"[graph] {name:14s} {dt*1e3:9.1f} ms "
                  f"({dt*1e3/multi_source:7.1f} ms/query)")

    if profile_dir:
        jax.profiler.stop_trace()
        print(f"[graph] wrote a profiler trace under {profile_dir} "
              "(open in TensorBoard or ui.perfetto.dev)")

    if verify:
        if "bfs_bsp" in results and "bfs_fast" in results:
            p_bsp = eng.gather_vertex_field(results["bfs_bsp"][0][0])
            p_fast = eng.gather_vertex_field(results["bfs_fast"][0][0])
            same = ((p_bsp < 2 ** 30) == (p_fast < 2 ** 30)).all()
            print(f"[verify] BFS reachability bsp==fast: {bool(same)}")
        if "pagerank_bsp" in results and "pagerank_fast" in results:
            r_bsp = eng.gather_vertex_field(results["pagerank_bsp"][0][0])
            r_fast = eng.gather_vertex_field(results["pagerank_fast"][0][0])
            rel = np.abs(r_bsp - r_fast).max() / r_bsp.max()
            print(f"[verify] PageRank bsp-vs-fast max rel diff: {rel:.2e}")
        # async-vs-bsp cross-checks when both modes ran
        if "bfs_async" in results and "bfs_fast" in results:
            pa = eng.gather_vertex_field(results["bfs_async"][0][0])
            pf = eng.gather_vertex_field(results["bfs_fast"][0][0])
            same = ((pa < 2 ** 30) == (pf < 2 ** 30)).all()
            print(f"[verify] BFS reachability async==fast: {bool(same)}")
        if "pagerank_async" in results and "pagerank_bsp" in results:
            ra = eng.gather_vertex_field(results["pagerank_async"][0][0])
            rb = eng.gather_vertex_field(results["pagerank_bsp"][0][0])
            rel = np.abs(ra - rb).max() / rb.max()
            print(f"[verify] PageRank bsp-vs-async max rel diff: {rel:.2e}")
        if "cc_async" in results and "cc" in results:
            la = eng.gather_vertex_field(results["cc_async"][0][0])
            lb = eng.gather_vertex_field(results["cc"][0][0])
            print(f"[verify] CC labels async==bsp: "
                  f"{bool((la == lb).all())}")
        if "sssp_async" in results and "sssp" in results:
            da = eng.gather_vertex_field(results["sssp_async"][0][0])
            db = eng.gather_vertex_field(results["sssp"][0][0])
            print(f"[verify] SSSP dist async==bsp: "
                  f"{bool((da == db).all())}")
        if "kcore" in results:
            kmax = int(results["kcore"][0][1])
            print(f"[verify] k-core degeneracy: {kmax}")
        if "betweenness" in results:
            bc0 = float(eng.gather_vertex_field(
                results["betweenness"][0][0])[0])
            print(f"[verify] betweenness delta_s(s) == 0: {bc0 == 0.0}")
        if "triangles" in results:
            tri = eng.gather_vertex_field(results["triangles"][0][0])
            total = int(results["triangles"][0][1])
            print(f"[verify] triangles sum/3 == total: "
                  f"{int(tri.sum()) // 3 == total} ({total:,})")
        if multi_source:
            mb = eng.gather_batched_vertex_field(
                results[f"bfs_fast_x{multi_source}"][0][0])
            same = ((mb[0] < 2 ** 30) == (p_fast < 2 ** 30)).all()
            print(f"[verify] multi-source BFS root0 == single-source: "
                  f"{bool(same)}")
    return results


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="urand16")
    ap.add_argument("--parts", type=int, default=len(jax.devices()))
    ap.add_argument("--pr-iters", type=int, default=50)
    ap.add_argument("--multi-source", type=int, default=0,
                    help="also run batched multi-source traversals "
                         "with this many roots")
    ap.add_argument("--layout", choices=("ell", "coo"), default="ell",
                    help="edge layout for the superstep hot loops: "
                         "blocked-ELL (backend-tuned local ops) or the "
                         "COO scatter reference path (escape hatch); "
                         "REPRO_LOCALOPS={auto,ref,kernel} further "
                         "overrides the localops dispatch")
    ap.add_argument("--exec-mode", choices=("all", "bsp", "async"),
                    default="all",
                    help="restrict to one superstep driver: bsp runs "
                         "the synchronous programs only, async the "
                         "stale-tolerant double-buffered ones; all "
                         "runs both and cross-checks them in verify")
    ap.add_argument("--obs", action="store_true",
                    help="also run each program with telemetry=True "
                         "(separate compile-cache entry) and report "
                         "per-round series + wire bytes per primitive")
    ap.add_argument("--profile-dir", default=None,
                    help="run the programs under a jax.profiler trace "
                         "written to this directory (device work named "
                         "by scope, host phases as repro.* spans)")
    ap.add_argument("--no-verify", action="store_true")
    args = ap.parse_args()
    run(args.graph, args.parts, pr_iters=args.pr_iters,
        verify=not args.no_verify, multi_source=args.multi_source,
        layout=args.layout, exec_mode=args.exec_mode, obs=args.obs,
        profile_dir=args.profile_dir)


if __name__ == "__main__":
    main()
