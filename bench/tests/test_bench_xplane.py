"""The reduction from a profiler trace to the benchmark's device
numbers, on a hand-made trace whose numbers are worked out by hand and
on a trace recorded on a TPU v5e (``pagerank_s10_v5e``: a traced run of
the PageRank cell's harness at scale 10, gzipped)."""

import gzip
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import xplane  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
RECORDED = sorted(FIXTURES.glob("*.xplane.pb.gz"))


def _handmade():
    from jax.profiler import ProfileData
    text = "".join(line for line in
                   (FIXTURES / "handmade.xspace.txt").read_text()
                   .splitlines(keepends=True) if not line.startswith("#"))
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_handmade_trace_reduces_to_the_numbers_worked_out_by_hand():
    r = xplane.reduce(_handmade())
    assert r.devices == 1
    assert r.window_s == pytest.approx(10000e-9)
    assert r.busy_s == pytest.approx(4000e-9)
    assert r.device_ops == [["fusion.1", pytest.approx(3500e-9)],
                            ["gather.2", pytest.approx(1000e-9)]]
    assert r.idle_gaps == [["launch", pytest.approx(3000e-9)],
                           ["window", pytest.approx(2000e-9)],
                           ["launch", pytest.approx(1000e-9)]]


def test_a_trace_without_window_or_device_reduces_to_nothing():
    pd = _handmade()
    assert xplane.reduce(pd, window="no-such-span") is None


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace_reduces_to_consistent_numbers(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    r = xplane.reduce(pd)
    assert r is not None and r.devices == 1
    assert 0 < r.busy_s <= r.window_s
    ops = [t for _, t in r.device_ops]
    assert ops == sorted(ops, reverse=True) and sum(ops) >= r.busy_s * 0.5
    gaps = [t for _, t in r.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r.window_s - r.busy_s + 1e-9
    assert {name for name, _ in r.idle_gaps} <= {"window", "launch"}
    assert all(" " not in name for name, _ in r.device_ops)
    # self times: no op's time is counted twice, so they sum to at most
    # the device time of the window
    assert sum(ops) <= r.window_s
