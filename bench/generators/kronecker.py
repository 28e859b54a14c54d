"""Graph500 Kronecker generator, on the device.

A copy of the Graph500 specification's reference generator (Graph500
Benchmark Specification, section 3, "Graph Generation"): every edge
draws one quadrant per bit level with the initiator probabilities
A, B, C (D = 1 - A - B - C).  The vertex labels are permuted from the
run's seed by the harness, as the specification's `randperm` does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def edges(key, cfg: dict):
    """(2, edgefactor * 2**scale) int32 undirected edges (sources, then
    destinations), unpermuted."""
    scale = cfg["scale"]
    m = cfg["edgefactor"] << scale
    a, b, c = cfg["A"], cfg["B"], cfg["C"]
    ab = a + b
    a_norm = a / ab
    c_norm = c / (1.0 - ab)

    def level(i, uv):
        u, v = uv
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        ii = jax.random.uniform(k1, (m,)) > ab
        jj = jax.random.uniform(k2, (m,)) > jnp.where(ii, c_norm, a_norm)
        return (u << 1) | ii.astype(jnp.int32), (v << 1) | jj.astype(jnp.int32)

    zero = jnp.zeros((m,), jnp.int32)
    u, v = jax.lax.fori_loop(0, scale, level, (zero, zero))
    return jnp.stack([u, v])
