"""A run whose timed path is broken underneath comes out not correct:
a superstep that returns its state unchanged, and an answer altered
where the program produces it.  The harness's look for a chip is
skipped; everything after it runs as on the chip, at a tiny size.
(The cells run on one chip with one query a launch, so no batch or
exchange between chips can be left out.)"""

import dataclasses
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from bench_tiny import tiny_plan  # noqa: E402

CELLS = ["kron21.bfs", "urand21.pagerank", "urand21.bfs"]


def _unchanged_step(prog):
    return dataclasses.replace(prog, step=lambda g, state: state)


def _altered_answer(prog):
    def outputs(state):
        first, *rest = prog.outputs(state)
        if jnp.issubdtype(first.dtype, jnp.integer):
            # the root's parent (the one vertex that is its own parent)
            # points at its neighbour in id order instead
            own = first == jnp.arange(first.shape[0], dtype=first.dtype)
            i = jnp.argmax(own)
            first = first.at[i].set((i + 1) % first.shape[0])
        else:
            first = first.at[0].multiply(1.5)
        return (first, *rest)
    return dataclasses.replace(prog, outputs=outputs)


@pytest.mark.parametrize("fault", [_unchanged_step, _altered_answer])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    from repro.core import api
    original = api.run_program
    monkeypatch.setattr(api, "run_program",
                        lambda prog, *a, **k: original(fault(prog), *a, **k))
    res = run.execute(tiny_plan(cell), 2 ** 31 + 9, 0.05, False,
                      run.peaks_for("TPU v5 lite"))
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1
