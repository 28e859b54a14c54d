"""The comparison that decides ``correct`` at a size a test run holds:
the program passes it, and its control fails it."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from bench_tiny import tiny_plan as tiny  # noqa: E402

CELLS = ["kron21.bfs", "urand21.pagerank", "urand21.bfs"]


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct_and_its_control_is_not(cell):
    peaks = run.peaks_for("TPU v5 lite")
    seed = 2 ** 31 + 5
    res = run.execute(tiny(cell), seed, 0.05, False, peaks)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    ctl = run.execute(tiny(cell), seed, 0.05, False, peaks,
                      control=True)
    assert not ctl["correct"], ctl["checks"]
    assert any(c["value"] > c["limit"] for c in ctl["checks"].values())
