"""The reduction of a profiler trace by the program's own names
(``scopes.py``): device self time per scope on a hand-made trace whose
numbers are worked out by hand, the program's host spans, idle gaps
named by the innermost open span, traces recorded on a TPU v5e with the
scope map of the program that ran (``*_s10_v5e``: traced runs of a
cell's harness at scale 10, gzipped, each beside its
``.scope_map.json``), the host build's spans in a CPU profile, and the
three readers that read scopes and spans."""

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import scopes  # noqa: E402
import xplane  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SCOPED = sorted(FIXTURES.glob("*.scope_map.json"))

STEP = "superstep.loop/superstep.step/localops.spmv_pull"
SCOPE_MAP = {"jit_fn": {"fusion.1": f"{STEP}/ell_in.b0",
                        "gather.2": f"{STEP}/reorder"}}


def _handmade(name):
    from jax.profiler import ProfileData
    text = "".join(line for line in (FIXTURES / name).read_text()
                   .splitlines(keepends=True) if not line.startswith("#"))
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_handmade_trace_reduces_by_scope_to_the_numbers_worked_out_by_hand():
    pd = _handmade("handmade_program.xspace.txt")
    r = scopes.reduce(pd, SCOPE_MAP)
    # fusion.1: [1000, 3000] in the window, then [4500, 6000] (it only
    # overlaps gather.2 [4000, 5000], so neither nests in the other)
    assert r.scope_s == {f"{STEP}/ell_in.b0": pytest.approx(3500e-9),
                         f"{STEP}/reorder": pytest.approx(1000e-9)}
    assert r.device_scopes == [[f"{STEP}/ell_in.b0", pytest.approx(3500e-9)],
                               [f"{STEP}/reorder", pytest.approx(1000e-9)]]
    assert r.under("localops.") == pytest.approx(4500e-9)
    assert r.under("exchange.") == 0
    # the second launch's idle [8000, 11000] is cut where the dispatch
    # span closes
    assert r.idle_spans == [["bench.launch", pytest.approx(2500e-9)],
                            ["bench.window", pytest.approx(2000e-9)],
                            ["bench.launch", pytest.approx(1000e-9)],
                            ["repro.engine.call", pytest.approx(500e-9)]]
    assert r.span_s("graph.ell") == pytest.approx(600e-9)
    assert r.span_s("graph.ell.ell_in") == pytest.approx(400e-9)
    assert r.span_s("engine.call") == pytest.approx(500e-9)
    assert r.span_s("engine.lower") is None
    # the benchmark's own reduction reads what it read without them
    x = xplane.reduce(pd)
    assert (x.busy_s, x.window_s) == (pytest.approx(4000e-9),
                                      pytest.approx(10000e-9))
    assert x.idle_gaps == [["launch", pytest.approx(3000e-9)],
                           ["window", pytest.approx(2000e-9)],
                           ["launch", pytest.approx(1000e-9)]]


def test_operations_a_scope_map_does_not_name_are_unscoped():
    pd = _handmade("handmade_program.xspace.txt")
    partial = {"jit_fn": {"fusion.1": SCOPE_MAP["jit_fn"]["fusion.1"]}}
    assert scopes.reduce(pd, partial).scope_s == {
        f"{STEP}/ell_in.b0": pytest.approx(3500e-9),
        "unscoped": pytest.approx(1000e-9)}
    other = {"jit_other": SCOPE_MAP["jit_fn"]}
    assert scopes.reduce(pd, other).scope_s == {
        "unscoped": pytest.approx(4500e-9)}


def test_without_scope_maps_or_window_only_the_spans_are_read():
    r = scopes.reduce(_handmade("handmade_program.xspace.txt"), None)
    assert r.scope_s is None and r.under("localops.") is None
    assert r.device_scopes == []
    assert r.span_s("graph.ell") == pytest.approx(600e-9)
    bare = scopes.reduce(_handmade("handmade.xspace.txt"), SCOPE_MAP,
                         window="no-such-span")
    assert bare.scope_s is None and bare.program_spans == []


def _recorded(scope_map_path):
    from jax.profiler import ProfileData
    trace = scope_map_path.with_name(
        scope_map_path.name.replace(".scope_map.json", ".xplane.pb.gz"))
    pd = ProfileData.from_serialized_xspace(
        gzip.decompress(trace.read_bytes()))
    return pd, json.loads(scope_map_path.read_text())


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.name)
def test_recorded_trace_maps_its_busy_time_to_declared_scopes(path):
    from repro.obs.registry import declared
    pd, maps = _recorded(path)
    r = scopes.reduce(pd, maps)
    busy = xplane.reduce(pd).busy_s
    scoped = sum(t for p, t in r.scope_s.items() if p != "unscoped")
    assert scoped >= 0.95 * busy
    assert r.scope_s.get("unscoped", 0.0) <= 0.05 * busy
    assert all(declared(part) for p in r.scope_s if p != "unscoped"
               for part in p.split("/"))
    # the local edge work is most of a round
    assert r.under("localops.") >= 0.5 * busy
    assert r.span_s("graph.ell") > 0
    assert {"repro.engine.call"} <= {n for n, _, _ in r.program_spans}


def _run_record(algo, reading, monkeypatch):
    monkeypatch.setattr(scopes, "reading", lambda run: reading)
    launches = [{"rounds": 7, "work": 30}, {"rounds": 5, "work": 10}]
    trace = xplane.Reduced(window_s=2.5, busy_s=2.0, devices=1,
                           device_ops=[], idle_gaps=[])
    return run.Run(algo=algo, n=2 ** 21, arcs=2 ** 26, setup_s=61.0,
                   partition_s=30.0, compile_s=4.0, window_s=2.0,
                   launches=launches, peak_bytes=6_000_000_000,
                   peaks=run.peaks_for("TPU v5 lite"), trace=trace)


def _read(name, rec):
    return run.load_module("metrics", name).read(rec)


def test_readers_compute_from_the_scopes_and_spans(monkeypatch):
    reading = scopes.Reading(
        scope_s={f"{STEP}/ell_in.b0": 1.25, f"{STEP}/reorder": 0.25,
                 "superstep.loop/superstep.step": 0.4, "unscoped": 0.1},
        program_spans=[["repro.graph.ell", 0, 20e9],
                       ["repro.graph.coo", 0, 5e9]])
    bfs_run = _run_record("bfs", reading, monkeypatch)
    assert _read("bfs_edge_ms_per_round", bfs_run) == \
        pytest.approx(1500 / 12)
    assert _read("partition_ell_s", bfs_run) == pytest.approx(20.0)
    assert _read("pagerank_spmv_roofline", bfs_run) is None
    pr_run = _run_record("pagerank", reading, monkeypatch)
    least_s = 285_212_672 * 12 / 819e9
    assert _read("pagerank_spmv_roofline", pr_run) == \
        pytest.approx(least_s / 1.5 * 100)
    assert _read("bfs_edge_ms_per_round", pr_run) is None


def test_readers_read_nothing_where_the_program_names_nothing(monkeypatch):
    # a program that keeps no scope map and annotates no span (the
    # benchmark laid over an older checkout), and an untraced run
    bare = scopes.Reading(scope_s=None, program_spans=[])
    for reading in (bare, None):
        for algo, names in (("bfs", ("bfs_edge_ms_per_round",
                                     "partition_ell_s")),
                            ("pagerank", ("pagerank_spmv_roofline",
                                          "partition_ell_s"))):
            rec = _run_record(algo, reading, monkeypatch)
            for name in names:
                assert _read(name, rec) is None, (name, reading)


def test_partition_ell_s_reads_the_host_builds_span(tmp_path, monkeypatch):
    import jax
    from repro.core import partition_graph
    from repro.graphs import urand_edges
    n = 1 << 9
    edges = urand_edges(n, 16 * n, seed=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        partition_graph(edges, n, parts=1)
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(scopes, "_LAST", [None, None])
    rec = run.Run(algo="bfs", n=n, arcs=len(edges), setup_s=1.0,
                  partition_s=1.0, compile_s=1.0, window_s=1.0,
                  trace=xplane.Reduced(window_s=1.0, busy_s=1.0, devices=1,
                                       device_ops=[], idle_gaps=[]))
    ell_s = _read("partition_ell_s", rec)
    reading = scopes.reading(rec)
    assert 0 < ell_s <= reading.span_s("graph.partition")
    assert ell_s >= max(reading.span_s(f"graph.ell.{name}")
                        for name in ("ell_in", "ell_out", "ell_dst",
                                     "ell_src"))
    assert (tmp_path / "reading.json").is_file()
