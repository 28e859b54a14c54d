"""The harness finds every configuration, traffic mix, program and
metric by the name BENCHMARK.json gives it, refuses to run without a
TPU or on a device kind it has no peaks for, and its readers compute
the metrics from what a run records."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import xplane  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_by_name(cell):
    plan = run.cell_plan(cell)
    cfg, traffic = plan["config"], plan["traffic"]
    assert cfg["name"] == plan["cell"]["config"]
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cfg["name"]]
    assert entry["file"] == f"bench/configs/{cfg['name']}.json"
    assert entry["reduced"] == cfg["reduced"]
    assert callable(run.load_module("generators", cfg["generator"]).edges)
    program = run.load_module("programs", traffic["program"])
    for name in ("params", "inputs", "check"):
        assert callable(getattr(program, name))
    reported = {m["name"] for m in plan["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    for m in plan["per_layer"]:
        assert m["moves"] in reported, m["name"]


@pytest.mark.parametrize("name", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(name):
    assert callable(run.load_module("metrics", name).read)


def test_unknown_names_are_refused():
    with pytest.raises(LookupError):
        run.cell_plan("no-such-cell")
    for kind in ("configs", "traffic"):
        with pytest.raises(LookupError):
            run.load_json(kind, "no-such-name")
    with pytest.raises(LookupError):
        run.load_module("metrics", "no_such_metric")


def _run_record(algo, trace=None):
    launches = [{"rounds": 7, "work": 30}, {"rounds": 5, "work": 10}]
    return run.Run(algo=algo, n=2 ** 21, arcs=2 ** 26, setup_s=61.0,
                   partition_s=30.0, compile_s=4.0, window_s=2.0,
                   launches=launches, peak_bytes=6_000_000_000,
                   peaks=run.peaks_for("TPU v5 lite"), trace=trace)


def test_readers_compute_from_the_run_record():
    def read(name, rec):
        return run.load_module("metrics", name).read(rec)

    trace = xplane.Reduced(window_s=2.5, busy_s=2.0, devices=1,
                           device_ops=[], idle_gaps=[])
    bfs_run = _run_record("bfs", trace)
    pr_run = _run_record("pagerank", trace)
    assert read("bfs_teps", bfs_run) == 20.0
    assert read("bfs_rounds", bfs_run) == 6.0
    assert read("bfs_busy_ms_per_round", bfs_run) == pytest.approx(2000 / 12)
    assert read("idle_share.bfs", bfs_run) == pytest.approx(20.0)
    assert read("pagerank_solve_s", pr_run) == 1.0
    assert read("pagerank_rounds", pr_run) == 6.0
    least_s = 285_212_672 * 12 / 819e9
    assert read("pagerank_edge_roofline", pr_run) == \
        pytest.approx(least_s / 2.0 * 100)
    assert read("peak_hbm_gb", bfs_run) == 6.0
    assert read("setup_s", bfs_run) == 61.0
    assert read("partition_s", bfs_run) == 30.0
    assert read("compile_s", bfs_run) == 4.0
    # a reader with nothing to read returns nothing
    for name in ("bfs_teps", "bfs_rounds", "idle_share.bfs",
                 "bfs_busy_ms_per_round"):
        assert read(name, pr_run) is None
    for name in ("pagerank_solve_s", "pagerank_edge_roofline",
                 "idle_share.pagerank"):
        assert read(name, bfs_run) is None
    untraced = _run_record("pagerank")
    for name in ("pagerank_edge_roofline", "idle_share.pagerank"):
        assert read(name, untraced) is None


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    # also from a directory that holds only BENCHMARK.json and bench/
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "artifacts",
                                                  ".jax_cache",
                                                  "__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for root in (REPO, tmp_path):
        r = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", CELLS[0],
             "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
            cwd=root, env=env, capture_output=True, text=True, timeout=120)
        assert r.returncode != 0
        assert r.stdout.strip() == ""
        assert "no TPU" in r.stderr


def _fake_devices(monkeypatch, kind, count):
    import jax
    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda: [dev] * count)


def test_unknown_device_kind_is_refused(monkeypatch):
    with pytest.raises(LookupError):
        run.peaks_for("TPU v99")
    _fake_devices(monkeypatch, "TPU v99", 1)
    with pytest.raises(SystemExit):
        run.require_tpu(1)
    _fake_devices(monkeypatch, "TPU v5 lite", 1)
    devices, peaks = run.require_tpu(1)
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        run.require_tpu(4)


@pytest.mark.parametrize("config", ["graph500-kron21", "gap-urand21"])
def test_every_seed_builds_the_same_program_shapes(config):
    from repro.core import partition_graph
    cfg = run.load_json("configs", config)
    cfg["scale"] = 10
    graphs = []
    for seed in (3, 2 ** 31 + 3):
        edges, arcs, n, perm = run.make_graph(cfg, seed)
        assert np.array_equal(np.sort(perm), np.arange(n))
        assert edges.shape == (cfg.get("edgefactor", cfg.get("degree"))
                               << 10, 2)
        assert arcs.shape == (2 * len(edges), 2)
        assert 0 <= edges.min() and edges.max() < n == 1024
        graphs.append((edges, partition_graph(arcs, n, cfg["parts"])))
    (e1, g1), (e2, g2) = graphs
    assert not np.array_equal(e1, e2)
    assert g1.layout_signature() == g2.layout_signature()
    assert (g1.n, g1.e_max) == (g2.n, g2.e_max)
    deg = lambda e: np.sort(np.bincount(e.ravel(), minlength=1024))
    assert np.array_equal(deg(e1), deg(e2))
