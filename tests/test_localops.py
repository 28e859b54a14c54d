"""Local-ops dispatch layer: parity of every primitive across its three
implementations (COO-scatter ref, blocked-ELL gather, Pallas kernel in
interpret mode), plus layout/property guards:

  * the blocked-ELL structures round-trip the EXACT edge multiset of the
    COO shards (both conformance graph families, property-tested over
    random graphs when hypothesis is installed);
  * whole programs produce identical results under ``layout="ell"`` and
    ``layout="coo"`` (the escape-hatch path compiles the same math);
  * ``push_combine`` gives what the scatter path gives on either of its
    routes (``ell_in`` at parts=1, ``ell_dst`` at parts>1), batched too;
  * REPRO_LOCALOPS mode resolution and the set_mode override;
  * the batched gather keeps every lane's answer, and the host build's
    fast stable sort builds the same graph as ``np.argsort``.

The primitives are pure per-partition compute (no collectives), so they
are exercised here directly on per-partition graph dicts - the
multi-partition exchange behaviour is covered by the oracle-conformance
gate, which runs the ELL path by default.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import oracle
from repro.core import GraphEngine, localops, partition_graph
from repro.core import graph as graph_mod
from repro.core.graph import ELL_BLOCK, ELL_LANE, ell_entries
from repro.launch.mesh import make_graph_mesh

INT_INF = 2 ** 30
MODES = ("ref", "auto", "kernel")


def _shard_dicts(g):
    """Per-partition graph dicts (what step() sees inside shard_map)."""
    arrs = g.device_arrays()
    return [{k: v[p] for k, v in arrs.items()} for p in range(g.parts)]


@pytest.fixture(scope="module", params=["urand", "smallworld"])
def graph(request):
    edges, n = oracle.family_edges(request.param, 384, 5)
    return request.param, edges, n, partition_graph(edges, n, parts=2)


# ---------------------------------------------------------------------------
# primitive parity: ref == ell == pallas-interpret (per partition)
# ---------------------------------------------------------------------------

def test_spmv_pull_parity(graph, rng):
    _, edges, n, g = graph
    x = rng.normal(size=g.n).astype(np.float32)
    want = np.zeros(g.n)
    np.add.at(want, edges[:, 1], x[edges[:, 0]].astype(np.float64))
    for p, garr in enumerate(_shard_dicts(g)):
        lo = p * g.n_local
        for mode in MODES:
            got = np.asarray(localops.spmv_pull(
                garr, g.ell_meta["ell_in"], jnp.asarray(x), mode=mode))
            np.testing.assert_allclose(got, want[lo:lo + g.n_local],
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"p={p} mode={mode}")


def test_frontier_pull_parity(graph, rng):
    _, edges, n, g = graph
    bits = rng.integers(0, 2 ** 32, g.n // 32, dtype=np.uint32)
    unv = rng.integers(0, 2, g.n).astype(bool)

    def in_frontier(v):
        return (bits[v >> 5] >> (v & 31)) & 1

    want = np.full(g.n, INT_INF, np.int64)
    for s, d in edges:
        if in_frontier(s) and unv[d]:
            want[d] = min(want[d], s)
    for p, garr in enumerate(_shard_dicts(g)):
        lo = p * g.n_local
        unv_p = jnp.asarray(unv[lo:lo + g.n_local])
        for mode in MODES:
            got = np.asarray(localops.frontier_pull(
                garr, g.ell_meta["ell_in"], jnp.asarray(bits), unv_p,
                mode=mode))
            np.testing.assert_array_equal(got, want[lo:lo + g.n_local],
                                          err_msg=f"p={p} mode={mode}")


@pytest.mark.parametrize("which,op", [
    ("ell_dst", "add"), ("ell_dst", "min"), ("ell_dst", "max"),
    ("ell_dst", "or"), ("ell_src", "min"), ("ell_src", "add"),
])
def test_scatter_combine_parity(graph, rng, which, op):
    _, edges, n, g = graph
    key_name = {"ell_dst": "out_dst_global", "ell_src": "in_src_global"}
    combine = {"add": np.add, "min": np.minimum, "max": np.maximum,
               "or": np.maximum}
    for p, garr in enumerate(_shard_dicts(g)):
        key = np.asarray(garr[key_name[which]])
        valid = key < g.n
        if op == "add":
            identity, vals = 0.0, np.where(
                valid, rng.normal(size=g.e_max), 0.0).astype(np.float32)
        elif op == "or":
            identity = False
            vals = valid & (rng.integers(0, 2, g.e_max) > 0)
        else:
            identity = INT_INF if op == "min" else 0
            vals = np.where(valid, rng.integers(0, 10 ** 6, g.e_max),
                            identity).astype(np.int32)
        want = np.full(g.n, identity,
                       np.float64 if op == "add" else np.int64)
        combine[op].at(want, key[valid], vals[valid])
        if op == "or":
            want = want > 0
        for mode in MODES:
            got = np.asarray(localops.scatter_combine(
                garr, g.ell_meta[which], jnp.asarray(vals), op,
                identity=identity, mode=mode))
            if op == "add":
                np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                           err_msg=f"p={p} mode={mode}")
            else:
                np.testing.assert_array_equal(got, want,
                                              err_msg=f"p={p} mode={mode}")


def test_scatter_combine_out_rows(graph, rng):
    """The per-local-source structure (ell_out) combines into n_local."""
    _, edges, n, g = graph
    for p, garr in enumerate(_shard_dicts(g)):
        dst = np.asarray(garr["out_dst_global"])
        srcl = np.asarray(garr["out_src_local"])
        valid = dst < g.n
        vals = np.where(valid, rng.normal(size=g.e_max), 0.0) \
            .astype(np.float32)
        want = np.zeros(g.n_local)
        np.add.at(want, srcl[valid], vals[valid].astype(np.float64))
        for mode in MODES:
            got = np.asarray(localops.scatter_combine(
                garr, g.ell_meta["ell_out"], jnp.asarray(vals), "add",
                identity=0.0, mode=mode))
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                       err_msg=f"p={p} mode={mode}")


# ---------------------------------------------------------------------------
# push_combine: one gather per ell_in slot at parts=1, ell_dst at parts>1
# ---------------------------------------------------------------------------

PUSH_IDENTITY = {"add": np.float32(0.0), "min": np.int32(INT_INF),
                 "max": np.int32(-1), "or": np.bool_(False)}


@functools.lru_cache(maxsize=None)
def _push_graph(family, parts):
    """A conformance graph plus self-loops and duplicate arcs; its arc
    count is no multiple of 128, so every partition has padding edges."""
    edges, n = oracle.family_edges(family, 300, 3)
    extra = np.array([[0, 0], [5, 5], [1, 2], [1, 2], [1, 2], [7, 3]])
    edges = np.concatenate([edges, extra]).astype(edges.dtype)
    return partition_graph(edges, n, parts=parts)


def _push_field(rng, op, shape):
    """A per-source field with about half its sources masked out."""
    if op == "or":
        return rng.integers(0, 2, shape) > 0
    if op == "add":   # whole numbers: every summation order is exact
        x = rng.integers(0, 8, shape).astype(np.float32)
    else:
        x = rng.integers(0, 10 ** 6, shape).astype(np.int32)
    return np.where(rng.random(shape) < 0.5, x, PUSH_IDENTITY[op])


def _push_ref(garr, g, x, op):
    """What the scatter path gives: ``x`` gathered into out-edge order,
    combined through ``ell_dst`` by the COO scatter."""
    srcl = np.asarray(garr["out_src_local"])
    valid = np.asarray(garr["out_dst_global"]) < g.n
    vals = np.where(valid, x[srcl], PUSH_IDENTITY[op])
    return np.asarray(localops.scatter_combine(
        garr, g.ell_meta["ell_dst"], jnp.asarray(vals), op,
        identity=PUSH_IDENTITY[op], mode="ref"))


@pytest.mark.parametrize("op", ["add", "min", "max", "or"])
@pytest.mark.parametrize("parts", [1, 2, 4])
@pytest.mark.parametrize("family", ["urand", "rmat"])
def test_push_combine_matches_the_scatter_path(rng, family, parts, op):
    g = _push_graph(family, parts)
    ell_in, ell_dst = g.ell_meta["ell_in"], g.ell_meta["ell_dst"]
    for p, garr in enumerate(_shard_dicts(g)):
        x = _push_field(rng, op, g.n_local)
        want = _push_ref(garr, g, x, op)
        for mode in MODES:
            got = np.asarray(localops.push_combine(
                garr, ell_in, ell_dst, jnp.asarray(x), op,
                identity=PUSH_IDENTITY[op], mode=mode))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"p={p} mode={mode}")
        if parts == 1 and op == "add":
            # same slots in the same order as ell_dst: the f32 sums of
            # arbitrary values are bit-identical to the edge-order path
            xf = rng.normal(size=g.n_local).astype(np.float32)
            srcl = np.asarray(garr["out_src_local"])
            valid = np.asarray(garr["out_dst_global"]) < g.n
            edge_order = localops.scatter_combine(
                garr, ell_dst, jnp.asarray(np.where(valid, xf[srcl], 0.0)),
                "add", identity=np.float32(0.0))
            np.testing.assert_array_equal(
                np.asarray(localops.push_combine(
                    garr, ell_in, ell_dst, jnp.asarray(xf), "add",
                    identity=np.float32(0.0))),
                np.asarray(edge_order))


@pytest.mark.parametrize("op", ["add", "min", "max", "or"])
@pytest.mark.parametrize("parts", [1, 2])
def test_push_combine_batched_matches_per_lane(rng, parts, op):
    """A vmapped (lanes, n_local) field gives each lane's own answer."""
    g = _push_graph("rmat", parts)
    ell_in, ell_dst = g.ell_meta["ell_in"], g.ell_meta["ell_dst"]
    garr = _shard_dicts(g)[-1]
    xs = _push_field(rng, op, (3, g.n_local))

    def one(x):
        return localops.push_combine(garr, ell_in, ell_dst, x, op,
                                     identity=PUSH_IDENTITY[op])

    got = np.asarray(jax.jit(jax.vmap(one))(jnp.asarray(xs)))
    for b, x in enumerate(xs):
        np.testing.assert_array_equal(got[b], _push_ref(garr, g, x, op),
                                      err_msg=f"lane {b}")


# ---------------------------------------------------------------------------
# blocked-ELL layout properties
# ---------------------------------------------------------------------------

def _check_ell_roundtrip(edges, n, parts):
    """Every ELL structure must hold EXACTLY the COO edge multiset."""
    g = partition_graph(edges, n, parts)
    in_valid = g.in_src_global < g.n
    out_valid = g.out_dst_global < g.n
    for name in ("ell_in", "ell_out", "ell_dst", "ell_src"):
        meta = g.ell_meta[name]
        # structural invariants of the bucketed layout
        assert sum(r for r, _ in meta.buckets) == meta.n_rows
        assert all(r % ELL_BLOCK == 0 for r, _ in meta.buckets)
        assert all(k % ELL_LANE == 0 for _, k in meta.buckets)
        widths = [k for _, k in meta.buckets]
        assert widths == sorted(widths, reverse=True), \
            f"{name}: degree buckets must be width-sorted"
        assert meta.slots == sum(r * k for r, k in meta.buckets)
        for p in range(parts):
            pairs = ell_entries(meta, g.ell_arrays[f"{name}_idx"][p],
                                g.ell_arrays[f"{name}_inv"][p])
            if name == "ell_in":    # (local dst row, global src id)
                ref = list(zip(g.in_dst_local[p][in_valid[p]].tolist(),
                               g.in_src_global[p][in_valid[p]].tolist()))
            elif name == "ell_out":  # (local src row, out-edge position)
                pos = np.flatnonzero(out_valid[p])
                ref = list(zip(g.out_src_local[p][pos].tolist(),
                               pos.tolist()))
            elif name == "ell_dst":  # (global dst row, out-edge position)
                pos = np.flatnonzero(out_valid[p])
                ref = list(zip(g.out_dst_global[p][pos].tolist(),
                               pos.tolist()))
            else:                    # (global src row, in-edge position)
                pos = np.flatnonzero(in_valid[p])
                ref = list(zip(g.in_src_global[p][pos].tolist(),
                               pos.tolist()))
            assert sorted(pairs) == sorted(ref), \
                f"{name} p={p}: edge multiset mismatch"


@pytest.mark.parametrize("family", ["urand", "smallworld"])
@pytest.mark.parametrize("parts", [1, 2, 4])
def test_ell_roundtrips_edge_multiset(family, parts):
    edges, n = oracle.family_edges(family, 384, 5)
    _check_ell_roundtrip(edges, n, parts)


try:
    from hypothesis import given, settings, strategies as st

    @given(st.integers(2, 8), st.integers(1, 6), st.integers(0, 2 ** 20),
           st.sampled_from([1, 2, 4]))
    @settings(max_examples=15, deadline=None)
    def test_ell_roundtrip_property(nv, deg, seed, parts):
        """Random urand graphs: the blocked-ELL layout is a lossless
        re-grouping of the COO shards for ANY degree distribution."""
        from repro.graphs import urand_edges
        n = 32 * nv
        edges = urand_edges(n, n * deg, seed=seed)
        _check_ell_roundtrip(edges, n, parts)
except ImportError:  # pragma: no cover - hypothesis is optional
    pass


# ---------------------------------------------------------------------------
# whole-program layout parity + mode resolution
# ---------------------------------------------------------------------------

def test_programs_match_across_layouts(graph):
    """layout="ell" and layout="coo" compile the same math."""
    _, edges, n, g = graph
    g1 = partition_graph(edges, n, parts=1)
    mesh = make_graph_mesh(1)
    eng_ell = GraphEngine(g1, mesh, layout="ell")
    eng_coo = GraphEngine(g1, mesh, layout="coo")
    for algo, variant, exact in (("bfs", "fast", True), ("cc", None, True),
                                 ("kcore", None, True),
                                 ("pagerank", "fast", False)):
        params = oracle.CONFORMANCE_PARAMS.get(
            (algo, variant or "default"), {})
        a = eng_ell.program(algo, variant, **params)(
            eng_ell.device_graph(),
            *([jnp.int32(3)] if algo == "bfs" else []))
        b = eng_coo.program(algo, variant, **params)(
            eng_coo.device_graph(),
            *([jnp.int32(3)] if algo == "bfs" else []))
        va = eng_ell.gather_vertex_field(a[0])
        vb = eng_coo.gather_vertex_field(b[0])
        if exact:
            np.testing.assert_array_equal(va, vb, err_msg=f"{algo}")
        else:
            np.testing.assert_allclose(va, vb, rtol=1e-5, atol=1e-9,
                                       err_msg=f"{algo}")


def test_programs_run_without_ell_build(graph):
    """partition_graph(build_ell_layout=False) must still serve every
    program: shards.ell() hands factories zero-slot placeholder metas
    and localops falls back to the COO scatter reference path."""
    _, edges, n, _ = graph
    g_no = partition_graph(edges, n, parts=1, build_ell_layout=False)
    assert not g_no.ell_meta and not g_no.ell_arrays
    g_full = partition_graph(edges, n, parts=1)
    mesh = make_graph_mesh(1)
    eng_no = GraphEngine(g_no, mesh)
    eng_full = GraphEngine(g_full, mesh)
    a, _ = eng_no.program("bfs", "fast")(eng_no.device_graph(),
                                         jnp.int32(3))
    b, _ = eng_full.program("bfs", "fast")(eng_full.device_graph(),
                                           jnp.int32(3))
    np.testing.assert_array_equal(eng_no.gather_vertex_field(a),
                                  eng_full.gather_vertex_field(b))


def test_mode_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_LOCALOPS", raising=False)
    localops.set_mode(None)
    assert localops.get_mode() == "auto"
    monkeypatch.setenv("REPRO_LOCALOPS", "ref")
    assert localops.get_mode() == "ref"
    localops.set_mode("kernel")         # override beats the env var
    assert localops.get_mode() == "kernel"
    localops.set_mode(None)
    assert localops.get_mode() == "ref"
    monkeypatch.setenv("REPRO_LOCALOPS", "bogus")
    with pytest.raises(ValueError):
        localops.get_mode()
    with pytest.raises(ValueError):
        localops.set_mode("bogus")
    monkeypatch.delenv("REPRO_LOCALOPS")
    assert localops.resolve(mode="ref") == "ref"
    assert localops.resolve(mode="kernel") == "pallas"
    assert localops.resolve(mode="auto", backend="tpu") == "ell"
    assert localops.resolve(mode="auto", backend="cpu") == "ell"


@pytest.mark.parametrize("batched", ["x", "idx", "both", "chunked"])
def test_take_batching_matches_per_lane(monkeypatch, rng, batched):
    """vmap of ``_take`` (lane axis leading) equals the per-lane gather,
    including when the flat offsets split the lanes into chunks."""
    lanes, n = 5, 300
    x = jnp.asarray(rng.random((lanes, n)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (lanes, 7, 40)), jnp.int32)
    if batched == "chunked":
        monkeypatch.setattr(localops, "_FLAT_LIMIT", 2 * n)
    x_ax = None if batched == "idx" else 0
    i_ax = 0 if batched in ("idx", "both", "chunked") else None
    xs = x if x_ax == 0 else x[0]
    ids = idx if i_ax == 0 else idx[0]
    got = jax.jit(jax.vmap(localops._take, in_axes=(x_ax, i_ax)))(xs, ids)
    want = np.stack([np.asarray(xs if x_ax is None else xs[b])[
        np.asarray(ids if i_ax is None else ids[b])] for b in range(lanes)])
    assert got.shape == (lanes, 7, 40)
    np.testing.assert_array_equal(np.asarray(got), want)


def _host_arrays(g):
    arrs = {k: v for k, v in vars(g).items() if isinstance(v, np.ndarray)}
    arrs.update(g.ell_arrays)
    return arrs


@pytest.mark.parametrize("family", ["urand", "rmat"])
@pytest.mark.parametrize("parts", [1, 2, 4])
def test_partition_matches_plain_argsort(monkeypatch, family, parts):
    """``_stable_argsort`` is ``np.argsort(kind="stable")`` sped up: a
    graph built with either is the same, array for array."""
    edges, n = oracle.family_edges(family, 1024, 9)
    keys = edges[:, 0] // 7
    np.testing.assert_array_equal(graph_mod._stable_argsort(keys),
                                  np.argsort(keys, kind="stable"))
    fast = partition_graph(edges, n, parts=parts)
    monkeypatch.setattr(graph_mod, "_stable_argsort",
                        lambda k: np.argsort(k, kind="stable"))
    plain = partition_graph(edges, n, parts=parts)
    assert fast.ell_meta == plain.ell_meta
    a, b = _host_arrays(fast), _host_arrays(plain)
    assert a.keys() == b.keys() and len(a) > 6
    for key in a:
        assert a[key].dtype == b[key].dtype, key
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
