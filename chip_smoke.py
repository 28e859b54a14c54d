"""Chip smoke test: the graph engine's main path on a TPU, checked
against NumPy references that share no code with the engine.

    PYTHONPATH=src python chip_smoke.py              # one chip
    PYTHONPATH=src python chip_smoke.py --chips 4    # four chips

One chip: urand22 (2^22 vertices, 2^26 edges, seed 42) at parts=1.
bfs and pagerank run in all three variants (bsp, fast, async) through
``GraphEngine.program``, then a ``GraphServer`` (buckets 1 and 8)
serves 16 BFS queries and one PageRank refresh.  Every answer is
checked; any failed check raises.

``--chips 4``: only the partitioned path, the same urand22 at parts=4
over a four-device mesh, so each program compares with its one-chip
run.  The same six programs run as telemetry builds, are checked
against the same references, and print their exchange wire bytes per
round.  (urand24, the four-chip size by memory, compiles within 16 GB
per device in tests/test_tpu_compile.py but is not run here: host
build and per-device gather time grow with the edge count, and urand22
at parts=4 already takes about four minutes on a v5e host.)

Every line is stamped with the seconds since start.  Seconds printed
on the way are informational: one cold run, not a benchmark.  The last
line of stdout is the JSON device record.  The script exits non-zero,
printing no record, when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 42
ROOT = 0               # source of the direct BFS launches
ALPHA = 0.85
INT_INF = 2 ** 30
PR_ITERS = 10          # bsp/fast: fixed budget, compared step for step
PR_REL_TOL = 1e-4      # tests/oracle.py's PageRank bound
ASYNC_STALENESS = 1
ASYNC_AGE_BOUND = 2 * ASYNC_STALENESS + 1
SERVE_QUERIES = 16
SERVE_CHECKED = 3

# (algo, variant) -> params: pagerank runs a fixed iteration budget
# (tol below reach), fast without bf16 compression, async to convergence
PROGRAMS = {
    ("bfs", "bsp"): {},
    ("bfs", "fast"): {},
    ("bfs", "async"): {},
    ("pagerank", "bsp"): {"iters": PR_ITERS, "tol": 1e-12},
    ("pagerank", "fast"): {"iters": PR_ITERS, "tol": 1e-12,
                           "compress": False},
    ("pagerank", "async"): {"iters": 300, "tol": 1e-7,
                            "staleness": ASYNC_STALENESS},
}


T_START = time.perf_counter()


def log(msg: str) -> None:
    print(f"{time.perf_counter() - T_START:7.1f}s {msg}", flush=True)


# ---------------------------------------------------------------------------
# NumPy references (no engine code)
# ---------------------------------------------------------------------------

def ref_bfs_levels(src, dst, n, root):
    """Hop distance of every vertex from ``root``; -1 if unreachable."""
    dist = np.full(n, -1, np.int32)
    dist[root] = 0
    frontier = np.zeros(n, bool)
    frontier[root] = True
    level = 0
    while True:
        nxt = np.zeros(n, bool)
        nxt[dst[frontier[src]]] = True
        nxt &= dist < 0
        if not nxt.any():
            return dist
        level += 1
        dist[nxt] = level
        frontier = nxt


def check_bfs(label, parents, src, dst, dist, root):
    """Reachability equals the reference, the root is its own parent,
    and every other parent is an in-neighbour one level up."""
    reached = parents < INT_INF
    if not np.array_equal(reached, dist >= 0):
        raise AssertionError(f"{label}: reachability differs from the "
                             "reference")
    if parents[root] != root:
        raise AssertionError(f"{label}: root {root} is not its own parent")
    child = reached.copy()
    child[root] = False
    p = parents[child]
    if not (dist[p] == dist[child] - 1).all():
        raise AssertionError(f"{label}: a parent is not one level up")
    # an edge (u, v) vouches for v when u is v's reported parent
    vouched = np.zeros(len(parents), bool)
    vouched[dst[parents[dst] == src]] = True
    if not vouched[child].all():
        raise AssertionError(f"{label}: a parent is not an in-neighbour")


def ref_pagerank(src, dst, n, iters):
    """Power iteration (dangling mass dropped): the rank after ``iters``
    steps, and the converged rank."""
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1.0), 0.0)
    rank = np.full(n, 1.0 / n)
    at_iters = None
    for it in range(1, 301):
        z = np.bincount(dst, weights=(rank * inv)[src], minlength=n)
        new = (1.0 - ALPHA) / n + ALPHA * z
        delta = np.abs(new - rank).sum()
        rank = new
        if it == iters:
            at_iters = rank
        if it >= iters and delta < 1e-13:
            break
    return at_iters, rank


def check_rank(label, got, ref):
    rel = float(np.abs(got - ref).max() / ref.max())
    if not rel < PR_REL_TOL:
        raise AssertionError(f"{label}: max rel err {rel:.3e} >= "
                             f"{PR_REL_TOL}")
    return rel


class References:
    """Reference answers for one edge list, computed on a worker thread
    (NumPy releases the GIL) while the host partitions the graph and the
    device compiles; each check waits for the answer it needs."""

    def __init__(self, edges, n, pool: ThreadPoolExecutor, root: int):
        self.src = np.ascontiguousarray(edges[:, 0])
        self.dst = np.ascontiguousarray(edges[:, 1])
        self.n = n
        self._pool = pool
        self._pagerank = pool.submit(ref_pagerank, self.src, self.dst, n,
                                     PR_ITERS)
        self._levels = {}
        self._submit_levels(root)

    def _submit_levels(self, root):
        if root not in self._levels:
            self._levels[root] = self._pool.submit(
                ref_bfs_levels, self.src, self.dst, self.n, root)
        return self._levels[root]

    def levels(self, root):
        return self._submit_levels(root).result()

    def check(self, algo, variant, fields, root):
        label = f"{algo}/{variant}"
        if algo == "bfs":
            check_bfs(label, fields["parents"], self.src, self.dst,
                      self.levels(root), root)
            return "parents ok"
        rank_iters, rank_converged = self._pagerank.result()
        if variant == "async":
            rel = check_rank(label, fields["rank"], rank_converged)
            age = int(fields["max_age"])
            if age > ASYNC_AGE_BOUND:
                raise AssertionError(f"{label}: max_age {age} > "
                                     f"{ASYNC_AGE_BOUND}")
            return f"rel err {rel:.3e} vs converged, max_age {age}"
        rel = check_rank(label, fields["rank"], rank_iters)
        return f"rel err {rel:.3e} vs {PR_ITERS} iterations"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def build(graph_name: str, parts: int, pool: ThreadPoolExecutor,
          root: int):
    """Generate the graph, start its references, partition and upload
    it; returns (engine, device graph, references, n)."""
    import jax
    from repro.configs import graph_workloads
    from repro.core import GraphEngine, partition_graph
    from repro.graphs import generate_edges
    from repro.launch.mesh import make_graph_mesh

    cfg = graph_workloads.ALL[graph_name]
    n = cfg.num_vertices
    t0 = time.perf_counter()
    edges = generate_edges(cfg, SEED)
    refs = References(edges, n, pool, root)
    g = partition_graph(edges, n, parts)
    t_host = time.perf_counter() - t0
    eng = GraphEngine(g, make_graph_mesh(parts))
    t0 = time.perf_counter()
    garr = jax.block_until_ready(eng.device_graph())
    log(f"[build] {graph_name}: n={n:,} edges={len(edges):,} "
        f"parts={parts} n_local={g.n_local:,} e_max={g.e_max:,}; host "
        f"{t_host:.1f}s, upload {time.perf_counter()-t0:.1f}s "
        f"(informational)")
    return eng, garr, refs, n


def fields_of(eng, prog, out):
    """Program outputs -> {name: host array or scalar}."""
    names = prog.program.output_names
    is_vertex = prog.program.output_is_vertex
    return {name: (eng.gather_vertex_field(o) if v else np.asarray(o)[()])
            for name, o, v in zip(names, out, is_vertex)}


def run_direct(eng, garr, refs, root, *, telemetry=False):
    """Launch the six programs; check each; returns {(algo, variant):
    fields}."""
    import jax
    results = {}
    for (algo, variant), params in PROGRAMS.items():
        prog = eng.program(algo, variant, telemetry=telemetry, **params)
        args = (garr, np.int32(root)) if algo == "bfs" else (garr,)
        t0 = time.perf_counter()
        prog.lower(*args).compile()     # the call below reuses it
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(prog(*args))
        t_run = time.perf_counter() - t0
        fields = fields_of(eng, prog, out)
        rounds = int(out[len(fields)])
        verdict = refs.check(algo, variant, fields, root)
        log(f"[direct] {algo}/{variant}: rounds={rounds} {verdict}; "
            f"compile {t_compile:.2f}s, run {t_run:.3f}s (informational)")
        if telemetry:
            wire = prog.run_telemetry(out[-1]).summary()[
                "wire_bytes_per_round"]
            log(f"[wire] {algo}/{variant}: bytes per round "
                + (", ".join(f"{op}={b:,}" for op, b in wire.items())
                   or "none"))
        results[(algo, variant)] = fields
    return results


def run_server(eng, refs, direct, n):
    """Serve 16 BFS queries and one PageRank refresh; check them."""
    from repro.serve import GraphServer, make_key, query

    pr_params = PROGRAMS[("pagerank", "fast")]
    server = GraphServer(eng, buckets=(1, 8))
    t0 = time.perf_counter()
    launches = server.warmup([make_key("bfs"),
                              make_key("pagerank", "fast", **pr_params)])
    log(f"[serve] warmed {launches} launches in "
        f"{time.perf_counter()-t0:.1f}s (informational)")
    roots = np.random.default_rng(SEED).choice(n, SERVE_QUERIES,
                                               replace=False)
    queries = [query("bfs", root=int(r)) for r in roots]
    queries.append(query("pagerank", "fast", **pr_params))
    t0 = time.perf_counter()
    results = server.serve(queries)
    log(f"[serve] {len(results)} answers in "
        f"{time.perf_counter()-t0:.2f}s (informational)")
    bad = [(r.qid, r.status, r.error) for r in results if not r.ok]
    if bad:
        raise AssertionError(f"served answers not ok: {bad}")
    for res in results[:SERVE_CHECKED]:
        refs.check("bfs", "fast", res.fields, res.root)
    log(f"[serve] {SERVE_CHECKED} BFS answers match the reference")
    rank = results[-1]["rank"]
    if not np.array_equal(rank, direct[("pagerank", "fast")]["rank"]):
        raise AssertionError("served PageRank differs from the direct "
                             "launch")
    log("[serve] PageRank refresh equals the direct launch")


def check_spread(garr, devices):
    """Every device-graph array has one shard on each device."""
    want = set(devices)
    for key, arr in garr.items():
        on = {s.device for s in arr.addressable_shards}
        if arr.sharding.device_set != want or on != want:
            raise AssertionError(f"{key} is not spread over {devices}")
    log(f"[mesh] device graph spread over {len(want)} devices")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: urand22 at parts=1, direct launches and the "
                         "server; 4: urand22 at parts=4, direct "
                         "launches only")
    args = ap.parse_args()

    import jax
    from repro.core import localops
    from repro.core.runtime import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    log(f"[device] jax {jax.__version__}; platform={dev.platform} "
        f"kind={dev.device_kind} count={len(devices)}; "
        f"localops={localops.resolve()}; compile cache {cache}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} devices")

    with ThreadPoolExecutor(max_workers=1) as pool:
        eng, garr, refs, n = build("urand22", args.chips, pool, ROOT)
        if args.chips == 4:
            check_spread(garr, devices[:4])
            run_direct(eng, garr, refs, ROOT, telemetry=True)
        else:
            direct = run_direct(eng, garr, refs, ROOT)
            run_server(eng, refs, direct, n)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
