"""GAP uniform random graph generator, on the device.

A copy of the GAP Benchmark Suite's `-u` generator (Beamer, Asanovic,
Patterson, arXiv:1508.03619, section 3): `degree * 2**scale` undirected
edges whose endpoints are drawn independently and uniformly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def edges(key, cfg: dict):
    """(2, degree * 2**scale) int32 undirected edges (sources, then
    destinations)."""
    n = 1 << cfg["scale"]
    m = cfg["degree"] << cfg["scale"]
    return jax.random.randint(key, (2, m), 0, n, dtype=jnp.int32)
