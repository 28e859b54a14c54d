"""Compile rehearsals for a TPU v5e, kept as tests.

The main path's programs compile against a described ``v5e:2x2``
topology (no chip attached) at the sizes ``chip_smoke.py`` runs:
urand22 on one chip and urand24 over four.  What the TPU compiler
refuses here — a program over the chip's 16 GB, a collective that does
not partition — fails in this file at no chip time.  Nothing runs, so
these say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.  Keep these tests in this
one file so that one worker loads the library for all of them.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core.api import GraphEngine
from repro.core.graph import abstract_graph

HBM_BYTES = 16e9          # one v5e chip
AVG_DEGREE = 16           # urand: 16 edges per vertex


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to a persistent
        # cache but cannot be read back without one: keep the cache off
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


def _engine(topo, scale: int, parts: int) -> GraphEngine:
    mesh = Mesh(np.array(topo.devices[:parts]), ("parts",))
    return GraphEngine(abstract_graph(1 << scale, AVG_DEGREE, parts), mesh)


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def _collectives(text: str) -> set[str]:
    return set(re.findall(r"\b(all-gather|all-to-all|all-reduce|"
                          r"reduce-scatter|collective-permute)\(", text))


@pytest.mark.parametrize("algo,variant", [("bfs", "fast"),
                                          ("pagerank", "fast")])
def test_urand22_fits_one_chip(topo, algo, variant):
    compiled = _engine(topo, 22, 1).program(algo, variant).aot()
    assert _device_bytes(compiled) < HBM_BYTES
    assert "tpu_custom_call" not in compiled.as_text()   # ell, no kernel


def test_urand22_server_bucket_fits_one_chip(topo):
    """The server's batch=8 BFS launch, lanes vmapped.  The ELL gathers
    keep the lane axis leading; with it minor the TPU pads it to 128 and
    this launch asks for about 60 GB."""
    compiled = _engine(topo, 22, 1).program("bfs", "fast", batch=8).aot()
    assert _device_bytes(compiled) < HBM_BYTES


# per program: the collectives the exchange is written with (lowered
# StableHLO), and those the v5e compiler keeps (it lowers pagerank's
# reduce-scatter to an all-reduce plus a slice)
EXCHANGES = {
    ("bfs", "fast"): ({"all_gather", "all_to_all"},
                      {"all-gather", "all-to-all"}),
    ("pagerank", "fast"): ({"reduce_scatter"}, {"all-reduce"}),
}


@pytest.mark.parametrize("algo,variant", sorted(EXCHANGES))
def test_urand24_fits_four_chips(topo, algo, variant):
    prog = _engine(topo, 24, 4).program(algo, variant)
    written, compiled_ops = EXCHANGES[(algo, variant)]
    lowered = prog.lower().as_text()
    assert written <= set(re.findall(r"stablehlo\.(\w+)", lowered))
    compiled = prog.aot()
    assert _device_bytes(compiled) < HBM_BYTES
    assert compiled_ops <= _collectives(compiled.as_text())
