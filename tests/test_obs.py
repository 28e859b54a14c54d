"""The observability subsystem (``repro/obs/``): span recorder + ring
semantics, engine telemetry series/wire accounting, Chrome trace export
and its schema validator, the ServeMetrics reconciliation contract, the
docs-drift gates for the registry tables, and the multi-device
acceptance drills (traced serve session with a schema-valid export;
telemetry-ON programs through the NumPy-oracle gate at parts {1,2,4}).

The in-process tests ride tier-1; the subprocess acceptance drills are
marked ``obs`` (their own lane in scripts/ci.sh).
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import REPO, run_with_devices
from repro.core import CheckpointRunner, GraphEngine, partition_graph
from repro.graphs import urand_edges
from repro.launch.mesh import make_graph_mesh
from repro.obs import (
    NULL_RECORDER,
    Event,
    PhaseSeries,
    Registry,
    RunTelemetry,
    Span,
    SpanRecorder,
    WireRecord,
    chrome_trace,
    derive_latency_cells,
    instruments_markdown_table,
    rollup,
    spans_markdown_table,
    trace_summary,
    validate_chrome_trace,
    write_trace,
)
from repro.obs import telemetry as obs_tel
from repro.serve import GraphServer
from repro.serve.metrics import ServeMetrics, percentiles

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

N, E, ROOT = 256, 2048, 3


@pytest.fixture(scope="module")
def eng():
    edges = urand_edges(N, E, seed=11)
    g = partition_graph(edges, N, parts=1)
    return GraphEngine(g, make_graph_mesh(1))


# -- percentile semantics (serve/metrics.py) -----------------------------


def test_percentiles_empty_cell_is_zero_not_nan():
    assert percentiles([]) == (0.0, 0.0, 0.0)


def test_percentiles_single_sample_is_that_sample():
    assert percentiles([0.25]) == (0.25, 0.25, 0.25)


def test_percentiles_two_samples_interpolate():
    p50, p95, p99 = percentiles([0.1, 0.3])
    assert p50 == pytest.approx(0.2)        # midpoint, by construction
    assert p95 == pytest.approx(0.1 + 0.95 * 0.2)
    assert p99 == pytest.approx(0.1 + 0.99 * 0.2)
    assert p50 < p95 < p99 <= 0.3


def test_metrics_rows_small_sample_cells():
    m = ServeMetrics()
    assert m.rows() == []                   # no cells -> no rows
    m.record("bfs_fast", 4, 0.010)
    (row,) = m.rows()
    assert row["count"] == 1
    assert row["p50_ms"] == row["p95_ms"] == row["p99_ms"] == 10.0
    m.record("bfs_fast", 4, 0.030)
    (row,) = m.rows()
    assert row["count"] == 2 and row["p50_ms"] == 20.0
    assert row["p50_ms"] < row["p95_ms"] < row["p99_ms"] <= 30.0


# -- span recorder -------------------------------------------------------


def test_span_recorder_ring_bounds_and_drop_counts():
    rec = SpanRecorder(maxlen=4)
    for i in range(6):
        rec.add_span("admission", "server", float(i), float(i) + 0.5, i=i)
        rec.event("shed", "server", i=i)
    assert len(rec.spans()) == 4 and rec.dropped_spans == 2
    assert len(rec.events()) == 4 and rec.dropped_events == 2
    assert [s.args["i"] for s in rec.spans()] == [2, 3, 4, 5]  # newest win
    rec.clear()
    assert rec.spans() == [] and rec.events() == []
    assert rec.dropped_spans == 0 and rec.dropped_events == 0


def test_span_context_manager_closes_and_stamps_errors():
    rec = SpanRecorder()
    with rec.span("validate", "server", qid=7) as sp:
        sp.args["extra"] = 1
    with pytest.raises(RuntimeError):
        with rec.span("dispatch", "executor"):
            raise RuntimeError("boom")
    s_ok, s_err = rec.spans()
    assert s_ok.kind == "validate" and s_ok.args == {"qid": 7, "extra": 1}
    assert s_ok.t1 >= s_ok.t0 and s_ok.dur >= 0.0
    assert s_err.args["error"] == "RuntimeError"
    # seq is recorder-global and monotone in start order
    assert s_err.seq > s_ok.seq


def test_null_recorder_is_inert():
    with NULL_RECORDER.span("admission", "server") as sp:
        sp.args["x"] = 1                    # body still works
    NULL_RECORDER.add_span("query", "server", 0.0, 1.0)
    NULL_RECORDER.event("shed", "server")
    assert not NULL_RECORDER.enabled
    assert NULL_RECORDER.spans() == [] and NULL_RECORDER.events() == []


# -- telemetry series + wire accounting ----------------------------------


def test_phase_series_trims_on_done_column():
    arr = np.zeros((6, 3), np.float32)      # 2 fixed cols + 1 probe
    arr[:4, 0] = 1.0                        # 4 rows actually written
    arr[3, 1] = 1.0                         # halted on the last one
    arr[:4, 2] = [5, 9, 2, 0]
    ps = PhaseSeries.from_array(arr, ("frontier",))
    assert ps.rounds == 4
    assert list(ps.halt()) == [0.0, 0.0, 0.0, 1.0]
    assert list(ps.probe("frontier")) == [5.0, 9.0, 2.0, 0.0]
    summ = ps.summary()
    assert summ["rounds"] == 4 and summ["halt_last"] == 1.0
    assert summ["frontier_max"] == 9.0
    assert summ["frontier_mean"] == pytest.approx(4.0)


def test_phase_series_width_mismatch_raises():
    with pytest.raises(ValueError):
        PhaseSeries.from_array(np.zeros((3, 3), np.float32),
                               ("a", "b"))  # expects 2 + 2 columns
    with pytest.raises(ValueError):
        PhaseSeries.from_array(np.zeros(6, np.float32), ())


def test_wire_record_phases_and_recording_context():
    rec = WireRecord()
    rec.add("stale", "junk", 999)           # recording() must clear this
    with obs_tel.recording(rec):
        obs_tel.phase("init")
        obs_tel.tap_wire("all_gather", np.zeros((4, 8), np.float32))
        obs_tel.phase("round")
        obs_tel.tap_wire("all_to_all", np.zeros(16, np.int32))
        obs_tel.tap_wire("all_to_all", np.zeros(16, np.int32))
    snap = rec.snapshot()
    assert snap == {
        "init/all_gather": {"bytes": 4 * 8 * 4, "taps": 1},
        "round/all_to_all": {"bytes": 2 * 16 * 4, "taps": 2},
    }
    assert rec.bytes_per_round() == 4 * 8 * 4 + 2 * 16 * 4
    # outside a recording context taps are no-ops (the off path)
    obs_tel.tap_wire("all_to_all", np.zeros(16, np.int32))
    assert rec.snapshot() == snap


def test_run_telemetry_summary_math():
    arr = np.zeros((3, 2), np.float32)
    arr[:, 0] = 1.0
    tel = RunTelemetry(
        series=PhaseSeries.from_array(arr),
        wire={"round/all_to_all": {"bytes": 100, "taps": 2},
              "init/all_gather": {"bytes": 7, "taps": 1}},
        wall_s=0.03)
    assert tel.wire_bytes_by_op() == {"all_to_all": 100}
    assert tel.wire_bytes_by_op(loop_only=False) == {
        "all_to_all": 100, "all_gather": 7}
    summ = tel.summary()
    assert summ["wire_bytes_per_round"] == {"all_to_all": 100}
    assert summ["wire_bytes_total"] == 100 * 3 + 7
    assert summ["round_ms_mean"] == pytest.approx(10.0)


# -- instrument registry + roll-up ---------------------------------------


def test_registry_refuses_undeclared_instruments():
    reg = Registry()
    reg.count("queries_submitted", 3)
    reg.gauge("epoch", 2)
    reg.observe("query_latency_ms", 12.5)
    with pytest.raises(KeyError):
        reg.count("made_up_counter")
    with pytest.raises(KeyError):
        reg.gauge("queries_submitted", 1)   # declared, but not a gauge
    snap = reg.snapshot()
    assert snap["counters"]["queries_submitted"] == 3
    assert snap["histograms"]["query_latency_ms"]["count"] == 1


def test_rollup_smoke():
    reg = Registry()
    reg.count("wal_appends", 2)
    rec = SpanRecorder()
    rec.add_span("admission", "server", 0.0, 0.001)
    text = rollup(reg, rec)
    assert "== obs roll-up ==" in text
    assert "wal_appends" in text and "server" in text


# -- Chrome trace export + schema validator ------------------------------


def _spanset():
    """admission(validate nested) + overlapping async queries + event."""
    spans = [
        Span("admission", "server", 0.000, 0.010, 1, {"qid": 0}),
        Span("validate", "server", 0.001, 0.002, 2, {}),
        Span("query", "server", 0.000, 0.050, 3,
             {"qid": 0, "status": "ok", "latency_s": 0.05}),
        Span("query", "server", 0.005, 0.040, 4,
             {"qid": 1, "status": "ok", "latency_s": 0.035}),
        Span("device", "device", 0.010, 0.030, 5, {"n": 2}),
    ]
    events = [Event("shed", "server", 0.020, 6, {"qid": 2})]
    return spans, events


def test_chrome_trace_export_shapes():
    spans, events = _spanset()
    trace = chrome_trace(spans, events)
    counts = validate_chrome_trace(trace)
    # 2 complete spans, 3 async spans (query x2 overlap + device), 1 inst
    assert counts["X"] == 2
    assert counts["b"] == counts["e"] == 3
    assert counts["i"] == 1
    assert counts["M"] >= 1
    evs = trace["traceEvents"]
    assert all(e["ts"] >= 0 for e in evs)   # relative to earliest stamp
    b_ids = {e["id"] for e in evs if e["ph"] == "b"}
    assert b_ids == {3, 4, 5}               # async pairs keyed by seq


def test_validator_rejects_malformed_traces():
    def bad(evs):
        with pytest.raises(ValueError):
            validate_chrome_trace({"traceEvents": evs})

    bad([{"ph": "X", "pid": 1, "tid": 0, "name": "a", "dur": 1.0}])
    bad([{"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 0.0}])
    # partial overlap on one track (nesting would be fine)
    bad([{"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 0.0,
          "dur": 10.0},
         {"ph": "X", "pid": 1, "tid": 0, "name": "b", "ts": 5.0,
          "dur": 10.0}])
    # unmatched / inverted async pairs
    bad([{"ph": "b", "pid": 1, "tid": 0, "name": "q", "cat": "server",
          "id": 1, "ts": 0.0}])
    bad([{"ph": "e", "pid": 1, "tid": 0, "name": "q", "cat": "server",
          "id": 1, "ts": 0.0}])
    # decreasing timestamps on one track
    bad([{"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 10.0,
          "dur": 1.0},
         {"ph": "X", "pid": 1, "tid": 0, "name": "b", "ts": 5.0,
          "dur": 1.0}])
    with pytest.raises(ValueError):
        validate_chrome_trace({"nope": []})
    # proper nesting on one track is NOT an error
    validate_chrome_trace({"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "a", "ts": 0.0,
         "dur": 10.0},
        {"ph": "X", "pid": 1, "tid": 0, "name": "b", "ts": 2.0,
         "dur": 3.0}]})


def test_write_trace_round_trip(tmp_path):
    spans, events = _spanset()
    trace = chrome_trace(spans, events)
    path = tmp_path / "sub" / "trace.json"
    counts = write_trace(path, trace)
    assert counts == validate_chrome_trace(trace)
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(trace))
    assert len(on_disk["traceEvents"]) == sum(counts.values())


# -- report: trace_summary + metrics reconciliation ----------------------


def test_trace_summary_counts_and_ranking():
    rec = SpanRecorder()
    rec.add_span("admission", "server", 0.0, 0.001)
    rec.add_span("admission", "server", 0.0, 0.002)
    rec.add_span("device", "device", 0.0, 0.5)
    rec.event("shed", "server")
    summ = trace_summary(rec, top=2)
    assert summ["spans_total"] == 3 and summ["events_total"] == 1
    assert summ["spans_per_kind"] == {"admission": 2, "device": 1}
    assert summ["spans_per_component"] == {"device": 1, "server": 2}
    assert summ["events_per_kind"] == {"shed": 1}
    assert summ["top_p99_ms"][0]["kind"] == "device"
    assert summ["top_p99_ms"][0]["p99_ms"] == pytest.approx(500.0)
    assert summ["dropped_spans"] == 0


def test_derive_latency_cells_counts_only_ok_queries():
    rec = SpanRecorder()
    rec.add_span("query", "server", 0.0, 0.1, label="bfs_fast", bucket=4,
                 status="ok", latency_s=0.125)
    rec.add_span("query", "server", 0.0, 0.1, label="bfs_fast", bucket=4,
                 status="timed_out", latency_s=9.0)
    rec.add_span("query", "server", 0.0, 0.1, label="pagerank_fast",
                 bucket=0, status="ok", latency_s=0.5)
    rec.add_span("admission", "server", 0.0, 0.1)
    assert derive_latency_cells(rec) == {
        ("bfs_fast", 4): [0.125],
        ("pagerank_fast", 0): [0.5],
    }


# -- docs drift: the registry tables in docs/API.md ----------------------


def test_docs_observability_span_table_is_current():
    content = open(os.path.join(REPO, "docs", "API.md")).read()
    assert spans_markdown_table() in content, (
        "docs/API.md observability span/event table drifted from "
        "obs.registry; regenerate it with "
        "repro.obs.spans_markdown_table()")


def test_docs_observability_instrument_table_is_current():
    content = open(os.path.join(REPO, "docs", "API.md")).read()
    assert instruments_markdown_table() in content, (
        "docs/API.md instrument table drifted from obs.registry; "
        "regenerate it with repro.obs.instruments_markdown_table()")


# -- compare.py never gates on observability blocks ----------------------


def test_compare_ignores_telemetry_and_trace_summary():
    sys.path.insert(0, REPO)
    try:
        from benchmarks import compare as cmp
    finally:
        sys.path.remove(REPO)
    row = {"algo": "bfs", "variant": "fast", "graph": "urand12",
           "parts": 2, "ms": 100.0, "rounds_to_converge": 8,
           "wire_mb_per_part": 0.5}
    new_row = dict(row, ms=104.0,
                   telemetry={"rounds": 8, "wire_bytes_total": 12345})
    old = {cmp._graph_key(row): row}
    new = {cmp._graph_key(new_row): new_row}
    lines, regressions = cmp.compare(old, new, threshold=1.25)
    assert regressions == [] and len(lines) == 2
    # ... and in the other direction (baseline has it, fresh doesn't)
    lines, regressions = cmp.compare(new, old, threshold=1.25)
    assert regressions == []
    # a serve meta gaining trace_summary is NOT config drift
    meta = {"localops": "auto", "mode": "fast", "launches": 16,
            "graph": "urand12", "parts": 2, "jax": "0.4.37",
            "device": "cpu"}
    assert not cmp.config_changed(meta, {**meta, "trace_summary": {}})


# -- engine telemetry end to end (parts=1, in-process) -------------------


def test_telemetry_on_is_bit_identical_to_off(eng):
    garr = eng.device_graph()
    off = eng.program("bfs", "fast")
    *outs, rounds = off(garr, jnp.int32(ROOT))
    on = eng.program("bfs", "fast", telemetry=True)
    tout = on(garr, jnp.int32(ROOT))
    assert len(tout) == len(outs) + 2       # trailing series output
    for a, b in zip((*outs, rounds), tout[:-1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tel = on.run_telemetry(tout[-1])
    assert tel.series.rounds == int(rounds) > 0
    assert tel.series.halt()[-1] == 1.0     # converged, not round-capped
    assert np.all(tel.series.halt()[:-1] == 0.0)
    assert "frontier" in tel.series.probe_names
    assert tel.series.probe("frontier")[0] >= 1.0
    assert tel.series.probe("frontier")[-1] == 0.0
    assert on.last_wall_s > 0.0 and tel.wall_s > 0.0
    summ = tel.summary()
    assert summ["rounds"] == int(rounds)
    assert "wire_bytes_total" in summ and "wall_ms" in summ


def test_telemetry_is_a_compile_cache_dimension(eng):
    off = eng.program("bfs", "fast")
    on = eng.program("bfs", "fast", telemetry=True)
    assert on is not off and on.telemetry and not off.telemetry
    assert eng.program("bfs", "fast", telemetry=True) is on
    assert eng.program("bfs", "fast") is off


def test_telemetry_composition_rules(eng):
    with pytest.raises(ValueError):
        eng.program("pagerank", "bsp", telemetry=True, static_iters=4)
    with pytest.raises(ValueError):
        eng.program("bfs", "fast", telemetry=True, batch=4)
    with pytest.raises(ValueError):
        eng.program("bfs", "fast").run_telemetry(None)


def test_checkpoint_runner_obs_events_and_telemetry(eng):
    garr = eng.device_graph()
    direct = eng.program("bfs", "fast")
    parents, rounds = direct(garr, jnp.int32(ROOT))
    rec = SpanRecorder()
    runner = CheckpointRunner(eng, "bfs", "fast", checkpoint_every=2,
                              faults="corrupt@r2p0:min seed=7",
                              telemetry=True, obs=rec)
    rep = runner.run(garr, jnp.int32(ROOT))
    assert rep.recoveries >= 1
    kinds = {e.kind for e in rec.events()}
    assert {"checkpoint", "fault_detection", "rollback"} <= kinds
    chunk_spans = [s for s in rec.spans() if s.kind == "chunk"]
    assert chunk_spans and all(s.component == "recovery"
                               for s in chunk_spans)
    # telemetry rolled back with the carry: no rows from discarded
    # chunks, and the recovered output is still the clean bits
    assert rep.telemetry is not None
    assert rep.telemetry["rounds"] == rep.rounds == int(rounds)
    np.testing.assert_array_equal(
        eng.gather_vertex_field(rep.outputs[0]),
        eng.gather_vertex_field(np.asarray(parents)))


# -- traced serving path (parts=1, in-process) ---------------------------


def test_traced_serve_spans_reconcile_with_metrics(eng):
    rec = SpanRecorder()
    server = GraphServer(eng, buckets=(4,), obs=rec)
    qids = [server.submit("bfs", root=r) for r in range(5)]
    qids.append(server.submit("pagerank"))
    server.drain()
    results = [server.results.pop(q) for q in qids]
    assert all(r.status == "ok" for r in results)

    spans = rec.spans()
    kinds = {s.kind for s in spans}
    assert {"admission", "validate", "coalesce_wait", "dispatch",
            "device", "demux", "query"} <= kinds
    # one query span per resolved query, one admission per submit
    assert sum(s.kind == "query" for s in spans) == len(qids)
    assert sum(s.kind == "admission" for s in spans) == len(qids)
    # THE reconciliation contract: latency cells derived from query
    # spans equal ServeMetrics' cells exactly (same floats, same order)
    assert derive_latency_cells(rec) == server.metrics.latencies()

    # a mutation records its span with the new epoch
    dels = server.dynamic_graph().sample_deletable(
        8, np.random.default_rng(0))
    stats = server.mutate(deletes=dels)
    (msp,) = [s for s in rec.spans() if s.kind == "mutation"]
    assert msp.args["epoch"] == server.epoch == 1
    assert msp.args["n_delete"] == stats.n_delete >= 1

    # a rejected admission leaves an event, not a span
    with pytest.raises(ValueError):
        server.submit("bfs", root=10 ** 9)
    assert any(e.kind == "rejected" for e in rec.events())

    # the recorder exports to a schema-valid trace round-trip
    trace = chrome_trace(rec.spans(), rec.events())
    counts = validate_chrome_trace(trace)
    assert counts["b"] == counts["e"] >= len(qids)
    summ = trace_summary(rec)
    assert summ["spans_per_kind"]["query"] == len(qids)
    assert summ["dropped_spans"] == 0 and summ["dropped_events"] == 0
    assert summ["top_p99_ms"]


def test_untraced_server_records_nothing(eng):
    server = GraphServer(eng, buckets=(4,))
    assert server.obs is NULL_RECORDER
    qid = server.submit("bfs", root=1)
    server.drain()
    assert server.results.pop(qid).status == "ok"
    assert NULL_RECORDER.spans() == [] and NULL_RECORDER.events() == []


def test_durability_and_recovery_spans(tmp_path):
    edges = urand_edges(128, 512, seed=3)
    g = partition_graph(edges, 128, parts=1)
    eng2 = GraphEngine(g, make_graph_mesh(1))
    rec = SpanRecorder()
    server = GraphServer(eng2, buckets=(4,), persistence=str(tmp_path),
                         obs=rec)
    dels = server.dynamic_graph().sample_deletable(
        4, np.random.default_rng(2))
    server.mutate(deletes=dels)
    server.durability.snapshot_now(server)
    kinds = {s.kind for s in rec.spans()}
    assert {"mutation", "wal_append", "snapshot"} <= kinds
    (wsp,) = [s for s in rec.spans() if s.kind == "wal_append"]
    assert wsp.component == "durability" and wsp.args["epoch"] == 1

    rec2 = SpanRecorder()
    srv2 = GraphServer.recover(tmp_path, buckets=(4,), obs=rec2)
    (rsp,) = [s for s in rec2.spans() if s.kind == "recovery"]
    assert rsp.args["epoch"] == srv2.epoch == 1
    # the recovered server's durability path stays instrumented
    dels2 = srv2.dynamic_graph().sample_deletable(
        4, np.random.default_rng(3))
    srv2.mutate(deletes=dels2)
    assert any(s.kind == "wal_append" for s in rec2.spans())


# -- multi-device acceptance drills (subprocess, obs lane) ---------------

_TRACED_SERVE_CODE = """
import sys
sys.path.insert(0, {tests_dir!r})
import json
import numpy as np
import oracle
from repro.core import GraphEngine, partition_graph
from repro.launch.mesh import make_graph_mesh
from repro.obs import (SpanRecorder, chrome_trace, derive_latency_cells,
                       trace_summary, validate_chrome_trace)
from repro.serve import GraphServer

parts = 2
edges, n = oracle.family_edges("urand", 384, 5)
g = partition_graph(edges, n, parts)
eng = GraphEngine(g, make_graph_mesh(parts))
rec = SpanRecorder()
server = GraphServer(eng, buckets=(8,), obs=rec)
qids = [server.submit("bfs", root=r) for r in range(12)]
qids.append(server.submit("pagerank"))
server.drain()
results = [server.results.pop(q) for q in qids]
assert all(r.status == "ok" for r in results), [r.status for r in results]
# served answers stay oracle-correct under tracing
oracle.check_conformance("bfs", "fast", dict(results[0].fields),
                         edges, n, 0)
# mutation under tracing
dels = server.dynamic_graph().sample_deletable(
    8, np.random.default_rng(1))
server.mutate(deletes=dels)

spans = rec.spans()
q_spans = [s for s in spans if s.kind == "query"]
assert len(q_spans) == len(qids), (len(q_spans), len(qids))
assert derive_latency_cells(rec) == server.metrics.latencies()
kinds = {{s.kind for s in spans}}
assert {{"admission", "validate", "coalesce_wait", "dispatch", "device",
         "demux", "mutation"}} <= kinds, kinds
counts = validate_chrome_trace(chrome_trace(spans, rec.events()))
assert counts["b"] == counts["e"] >= len(qids)
summ = trace_summary(rec)
assert summ["spans_per_kind"]["query"] == len(qids)
assert summ["dropped_spans"] == 0
print("TRACED-SERVE-OK", json.dumps(counts))
"""


@pytest.mark.obs
@pytest.mark.slow
def test_obs_traced_serve_acceptance():
    """A parts=2 traced serve session: answers stay correct, every
    pipeline stage leaves spans, the latency cells reconcile exactly,
    and the Chrome export passes the schema validator."""
    out = run_with_devices(_TRACED_SERVE_CODE.format(tests_dir=TESTS_DIR))
    assert "TRACED-SERVE-OK" in out


_TELEMETRY_SWEEP_CODE = """
import sys
sys.path.insert(0, {tests_dir!r})
import numpy as np
import jax.numpy as jnp
import oracle
from repro.core import GraphEngine, partition_graph, registry
from repro.launch.mesh import make_graph_mesh

n, seed, root = 384, 5, 3
edges, n = oracle.family_edges("urand", n, seed)
pairs = {{}}
for algo, variant in sorted(registry.available()):
    spec = registry.get_spec(algo, variant)
    if all(k == "scalar" for k in spec.input_kinds):
        pairs.setdefault(algo, (algo, variant))
pairs = list(pairs.values())
assert len(pairs) >= 3, pairs
for parts in (1, 2, 4):
    g = partition_graph(edges, n, parts)
    eng = GraphEngine(g, make_graph_mesh(parts))
    garr = eng.device_graph()
    for algo, variant in pairs:
        spec = registry.get_spec(algo, variant)
        params = oracle.CONFORMANCE_PARAMS.get((algo, variant), {{}})
        ins = (jnp.int32(root),) * len(spec.inputs)
        prog = eng.program(algo, variant, **params)
        *outs, rounds = prog(garr, *ins)
        tprog = eng.program(algo, variant, telemetry=True, **params)
        tout = tprog(garr, *ins)
        # telemetry-ON output bits == telemetry-OFF (the seed path)
        for a, b in zip((*outs, rounds), tout[:-1]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"{{algo}}/{{variant}} parts={{parts}}: telemetry build "
                "diverged from the plain build")
        tel = tprog.run_telemetry(tout[-1])
        assert tel.series.rounds == int(rounds), (algo, variant, parts)
        if parts > 1:
            # multi-part runs exchange every round; the trace-time tap
            # accounting must see it
            assert sum(tel.wire_bytes_by_op().values()) > 0, (
                algo, variant, parts)
        # ... and the telemetry run still passes the oracle gate
        p = prog.program
        fields = {{name: (eng.gather_vertex_field(o) if isv
                          else np.asarray(o)[()])
                   for name, o, isv in zip(p.output_names, tout[:-2],
                                           p.output_is_vertex)}}
        oracle.check_conformance(algo, variant, fields, edges, n, root)
        print(f"PASS {{algo}}/{{variant}} parts={{parts}} "
              f"rounds={{int(rounds)}}")
print("TELEMETRY-OK")
"""


@pytest.mark.obs
@pytest.mark.slow
def test_obs_telemetry_conformance_across_parts():
    """Telemetry-ON builds at parts {1,2,4}: bit-identical outputs to
    the plain builds, per-round series lengths matching the driver's
    round count, non-zero wire accounting on multi-part meshes, and
    NumPy-oracle conformance of the telemetry run itself."""
    out = run_with_devices(
        _TELEMETRY_SWEEP_CODE.format(tests_dir=TESTS_DIR))
    assert "TELEMETRY-OK" in out
