"""Names on the engine's work (``repro/obs/scopes.py``, ``obs/spans.py``):
device scopes read back from compiled HLO by ``op_scopes``, their
coverage of the compiled bfs/fast and pagerank/fast loops, the route
``localops.push_combine`` takes at one and two partitions, the refusal
of undeclared names, the program's host spans and the serve spans on
the profiler's clock, and the docs table of both."""

import json
import os
import re

import pytest

import jax
import jax.numpy as jnp

from conftest import REPO, run_with_devices
from repro.core import GraphEngine, partition_graph
from repro.graphs import urand_edges
from repro.launch.mesh import make_graph_mesh
from repro.obs import (
    SpanRecorder,
    annotate,
    compiled_scopes,
    device_scope,
    op_scopes,
    scopes_markdown_table,
)
from repro.obs.registry import declared
from repro.obs.scopes import UNSCOPED, _parse


def _nested(x, idx):
    with device_scope("superstep.init"):
        x = x + 1.0

    @device_scope("superstep.halt")
    def cond(c):
        return c[1] < 3

    def body(c):
        y, r = c
        with device_scope("superstep.step"), \
                device_scope("localops.spmv_pull"):
            with device_scope("ell_in.b0"):
                s = jnp.sin(y[idx]).sum()
            with device_scope("reorder"):
                y = y[::-1] + s
        return y, r + 1

    with device_scope("superstep.loop"):
        y, _ = jax.lax.while_loop(cond, body, (x, 0))
    with device_scope("superstep.outputs"):
        return y * 3.0


def test_op_scopes_reads_nested_scopes_from_compiled_hlo():
    text = jax.jit(_nested).lower(
        jnp.ones(128), jnp.arange(64) % 7).compile().as_text()
    scopes = op_scopes(text)
    instrs, _ = _parse(text)
    assert set(scopes) == set(instrs)
    found = set(scopes.values())
    step = "superstep.loop/superstep.step/localops.spmv_pull"
    assert {f"{step}/ell_in.b0", f"{step}/reorder",
            "superstep.loop/superstep.halt"} <= found
    # every path is made of declared names only
    assert all(declared(part) for path in found - {UNSCOPED}
               for part in path.split("/"))
    # the sine of the gather lives in the bucket's scope, whatever XLA
    # fused it into
    sine = [n for n, (_, _, op, *_) in instrs.items() if op == "sine"]
    assert sine and all(scopes[n] == f"{step}/ell_in.b0" for n in sine)


def test_op_scopes_takes_a_fusions_root_and_falls_back_for_added_ops():
    text = "\n".join([
        "HloModule jit_fn, is_scheduled=true",
        "",
        "%fused (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  %a = f32[4]{0} add(%p, %p), metadata={op_name="jit(fn)/'
        'superstep.init/add"}',
        '  ROOT %m = f32[4]{0} multiply(%a, %a), metadata={op_name='
        '"jit(fn)/vmap(superstep.loop)/superstep.step/bfs.pull/mul"}',
        "}",
        "",
        "ENTRY %main (x: f32[4]) -> (f32[4], f32[4]) {",
        "  %x = f32[4]{0} parameter(0)",
        '  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused, '
        'metadata={op_name="jit(fn)/superstep.init/add"}',
        "  %copy.1 = f32[4]{0} copy(%fusion.3)",
        '  %neg = f32[4]{0} negate(%x), metadata={op_name="jit(fn)/neg"}',
        "  ROOT %t = (f32[4]{0}, f32[4]{0}) tuple(%copy.1, %neg)",
        "}",
    ])
    scopes = op_scopes(text)
    # the root's name, a batched program's vmap(...) unwrapped
    assert scopes["fusion.3"] == "superstep.loop/superstep.step/bfs.pull"
    assert scopes["copy.1"] == scopes["fusion.3"]      # from its operand
    assert scopes["neg"] == UNSCOPED                    # named, undeclared
    assert scopes["x"] == UNSCOPED                      # nothing to go on


@pytest.fixture(scope="module")
def eng():
    n = 1 << 9
    g = partition_graph(urand_edges(n, 16 * n, seed=9), n, parts=1)
    return GraphEngine(g, make_graph_mesh(1))


CONTROL = ("while", "conditional", "call")
BUCKET = re.compile(r"ell_\w+\.b\d+")


def _loop_computations(instrs, comps):
    """Computations a ``while`` runs: its body and condition, and the
    branches and calls in them.  Fused computations and reducers are
    left out: their instructions are no operations of their own."""
    todo = [c for (_, _, op, _, called, _) in instrs.values()
            if op == "while" for c in called if c in comps]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for name in comps[c]:
            _, _, op, _, called, _ = instrs[name]
            if op in CONTROL:
                todo += [k for k in called if k in comps]
    return seen


@pytest.mark.parametrize("algo", ["bfs", "pagerank"])
def test_every_loop_instruction_maps_to_a_declared_scope(eng, algo):
    compiled = eng.program(algo, "fast").aot()
    text = compiled.as_text()
    scopes = op_scopes(text)
    instrs, comps = _parse(text)
    loop = _loop_computations(instrs, comps)
    assert loop
    in_loop = [n for c in loop for n in comps[c]]
    unscoped = [n for n in in_loop if scopes[n] == UNSCOPED]
    assert not unscoped, unscoped[:10]
    assert all(scopes[n].startswith("superstep.loop") for n in in_loop)
    # a bucket's or the reorder's work is always inside its primitive
    for name, path in scopes.items():
        parts = path.split("/")
        for i, part in enumerate(parts):
            if BUCKET.fullmatch(part) or part == "reorder":
                assert any(p.startswith("localops.") for p in parts[:i]), \
                    (name, path)
    # every non-empty bucket of the structure the program reads is named
    prim = ("localops.frontier_pull" if algo == "bfs"
            else "localops.push_combine")
    ell = "ell_in"
    want = {f"{prim}/{ell}.b{i}"
            for i, (_, k) in enumerate(eng.g.ell(ell).buckets) if k}
    have = {"/".join(p for p in path.split("/")
                     if p.startswith("localops.") or BUCKET.fullmatch(p))
            for path in scopes.values()}
    assert want <= have
    # the executable compile() returned is kept for the profiler reader
    assert compiled_scopes()["jit_fn"] == scopes


GATHER = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]"
                    r"\S*\s+gather\(", re.M)


def _gathers(text):
    """``{instruction: output dims}`` of every gather in an HLO text."""
    return {m.group(1): [int(d) for d in m.group(2).split(",") if d]
            for m in GATHER.finditer(text)}


def _push_routes(scopes):
    """The ELL structures read under ``localops.push_combine``."""
    return {m.group(1) for path in scopes.values()
            for m in [re.search(r"localops\.push_combine/(ell_\w+)\.b\d+",
                                path)] if m}


@pytest.mark.parametrize("algo", ["bfs", "pagerank"])
def test_push_combine_gathers_once_per_ell_in_slot_at_one_partition(eng,
                                                                    algo):
    text = eng.program(algo, "fast").aot().as_text()
    scopes = op_scopes(text)
    assert _push_routes(scopes) == {"ell_in"}
    # no per-arc gather into edge order is left outside the local ops
    per_arc = [name for name, dims in _gathers(text).items()
               if eng.g.e_max in dims
               and "localops." not in scopes[name]]
    assert not per_arc, [(n, scopes[n]) for n in per_arc]


_PARTS2_CODE = """
import json
from repro.core import GraphEngine, partition_graph
from repro.graphs import urand_edges
from repro.launch.mesh import make_graph_mesh
from repro.obs import op_scopes
n = 1 << 9
g = partition_graph(urand_edges(n, 16 * n, seed=9), n, parts=2)
eng = GraphEngine(g, make_graph_mesh(2))
print(json.dumps({algo: sorted(set(op_scopes(
    eng.program(algo, "fast").aot().as_text()).values()))
    for algo in ("bfs", "pagerank")}))
"""


def test_push_combine_goes_through_ell_dst_at_two_partitions():
    out = json.loads(run_with_devices(_PARTS2_CODE, devices=2)
                     .strip().splitlines()[-1])
    for algo, paths in out.items():
        assert _push_routes(dict(enumerate(paths))) == {"ell_dst"}, algo


def test_undeclared_names_are_refused():
    with pytest.raises(KeyError):
        device_scope("localops.no_such_primitive")
    with pytest.raises(KeyError):
        annotate("engine.no_such_phase")
    device_scope("ell_dst.b12")
    annotate("graph.ell.ell_src")


def _profile(tmp_path, fn):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    return [ev.name for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events]


def test_host_build_and_serve_spans_land_in_a_profiler_trace(tmp_path):
    n = 1 << 9
    edges = urand_edges(n, 16 * n, seed=4)
    rec = SpanRecorder()

    def work():
        partition_graph(edges, n, parts=1)
        with rec.span("admission", "server"):
            with rec.span("validate", "server"):
                pass

    names = set(_profile(tmp_path, work))
    assert {"repro.graph.partition", "repro.graph.coo", "repro.graph.ell",
            "repro.graph.ell.ell_in", "repro.graph.ell.ell_out",
            "repro.graph.ell.ell_dst", "repro.graph.ell.ell_src",
            "repro.server.admission", "repro.server.validate"} <= names
    # the recorder's own record is unchanged
    assert [s.kind for s in rec.spans()] == ["validate", "admission"]


def test_docs_observability_scopes_table_is_current():
    content = open(os.path.join(REPO, "docs", "API.md")).read()
    assert scopes_markdown_table() in content, (
        "docs/API.md device scope / program span table drifted from "
        "obs.registry; regenerate it with "
        "repro.obs.scopes_markdown_table()")
