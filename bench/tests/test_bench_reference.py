"""The benchmark's NumPy references and counts on tiny hand-checked
graphs."""

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import roofline  # noqa: E402
import run  # noqa: E402

bfs = run.load_module("programs", "bfs")
pagerank = run.load_module("programs", "pagerank")

# 0-1, 1-2, 2-3, 0-2 form one component; 4-5 another; 6 is isolated
EDGES = np.array([[0, 1], [1, 2], [2, 3], [0, 2], [4, 5]], np.int32)
N = 7
UNREACHED = 2 ** 30


def test_bfs_levels_by_hand():
    assert bfs.levels(EDGES, N, 0).tolist() == [0, 1, 1, 2, -1, -1, -1]
    assert bfs.levels(EDGES, N, 5).tolist() == [-1, -1, -1, -1, 1, 0, -1]


def test_graph500_work_counts_input_edges_in_the_component():
    assert bfs.work(EDGES, bfs.levels(EDGES, N, 3)) == 4
    assert bfs.work(EDGES, bfs.levels(EDGES, N, 4)) == 1
    # a self-loop and a duplicate inside the component count too
    extra = np.concatenate([EDGES, [[1, 1], [0, 1]]]).astype(np.int32)
    assert bfs.work(extra, bfs.levels(extra, N, 0)) == 6


@pytest.mark.parametrize("parents, errors", [
    ([0, 0, 0, 2, UNREACHED, UNREACHED, UNREACHED], 0),
    ([0, 0, 0, 2, -1, -1, -1], 0),              # any out-of-range = unreached
    ([0, 0, 1, 2, UNREACHED, UNREACHED, UNREACHED], 1),   # same level
    ([0, 0, 0, 1, UNREACHED, UNREACHED, UNREACHED], 1),   # not an edge
    ([1, 0, 0, 2, UNREACHED, UNREACHED, UNREACHED], 1),   # root moved
    ([0, 0, 0, UNREACHED, UNREACHED, UNREACHED, UNREACHED], 1),  # missed
    ([0, 0, 0, 2, 5, UNREACHED, UNREACHED], 1),   # reached too much
])
def test_bfs_tree_errors_by_hand(parents, errors):
    dist = bfs.levels(EDGES, N, 0)
    assert bfs.tree_errors(EDGES, N, 0, np.array(parents), dist) == errors


def test_bfs_control_breaks_the_level_rule():
    # from root 0, vertex 4 (level 1) waits a round; by then 2 (level 2)
    # expands too, and claims 3 (level 2) as the least discovering id
    edges = np.array([[0, 1], [0, 4], [1, 2], [2, 3], [4, 3]], np.int32)
    rec = {"inputs": (np.int32(0),), "rounds": 0, "outputs": {}}
    (ctl,) = bfs.control({}, edges, 5, [rec])
    parents = ctl["outputs"]["parents"]
    assert parents.tolist() == [0, 0, 1, 2, 0]
    assert bfs.tree_errors(edges, 5, 0, parents, bfs.levels(edges, 5, 0)) \
        == 1


def test_bfs_search_keys_are_a_uniform_draw_relabelled_by_the_seed():
    # 0-1, 1-2, 2-3 and a self-loop on 4; 5 and 6 are isolated: only
    # 0..3 have degree >= 1 once self-loops are left out
    edges = np.array([[0, 1], [1, 2], [2, 3], [4, 4]], np.int32)
    traffic, cfg = {"search_keys": 4}, {"structure_seed": 500}
    draws = []
    for seed in (1, 2, 2 ** 31 + 11):
        perm = np.random.default_rng(seed).permutation(7).astype(np.int32)
        launches = bfs.inputs(traffic, cfg, perm[edges], perm)
        roots = np.array([int(k) for (k,) in launches])
        # the first warms up; the window searches all four from the first
        assert roots[0] == roots[1] and len(roots) == 5
        # back in the structure's labels: the same vertices, same order
        draws.append(np.argsort(perm)[roots[1:]].tolist())
    assert draws[0] == draws[1] == draws[2]
    assert sorted(draws[0]) == [0, 1, 2, 3]
    # no filter beyond degree: over many structure seeds every vertex
    # of degree >= 1 comes first about as often as the others
    firsts = np.bincount(
        [bfs.search_keys({"search_keys": 1}, {"structure_seed": s},
                         edges, np.arange(7))[0] for s in range(400)],
        minlength=7)
    assert firsts[4:].sum() == 0 and firsts[:4].min() > 70


def test_pagerank_by_hand():
    # a triangle and an isolated vertex: the triangle keeps 1/4 each,
    # the isolated vertex falls to (1 - d) / n
    tri = np.array([[0, 1], [1, 2], [2, 0]], np.int32)
    rank = pagerank.power_iteration(tri, 4, 0.85, 3)
    np.testing.assert_allclose(rank, [0.25, 0.25, 0.25, 0.0375])
    assert pagerank.residual_l1(tri, 4, 0.85, rank) == pytest.approx(0)
    assert pagerank.residual_l1(tri, 4, 0.85, np.full(4, 0.25)) == \
        pytest.approx(0.2125)
    assert pagerank.max_rel_err(rank * 1.01, rank) == pytest.approx(0.01)


def test_pagerank_check_compares_at_the_reported_step_count():
    traffic = {"damping": 0.85, "limits": {"pr_max_rel_err": 1e-3,
                                           "pr_residual_l1": 1e-4}}
    exact = pagerank.power_iteration(EDGES, N, 0.85, 60)
    ok = {"rounds": 60, "outputs": {"rank": exact.astype(np.float32)}}
    early = {"rounds": 60, "outputs": {
        "rank": pagerank.power_iteration(EDGES, N, 0.85, 1)}}
    v = pagerank.check(traffic, EDGES, N, [ok])
    assert v["failed"] == 0 and v["checks"]["pr_max_rel_err"][0] < 1e-6
    v = pagerank.check(traffic, EDGES, N, [ok, early])
    assert v["failed"] == 1 and v["checks"]["pr_max_rel_err"][0] > 1e-3


def test_pagerank_roofline_bytes_from_sizes_alone():
    assert roofline.pagerank_iteration_bytes(2 ** 21, 2 ** 26) == \
        4 * 2 ** 26 + 8 * 2 ** 21 == 285_212_672
    assert roofline.pagerank_iteration_bytes(7, 10) == 96
