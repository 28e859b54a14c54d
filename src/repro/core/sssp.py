"""Distributed SSSP (Bellman-Ford with frontier pruning).

One of the paper's "future work: extend to the full NWGraph algorithm
set" items - included here as a third traversal-family algorithm.  Edge
weights are synthesized deterministically from endpoint ids (uniform in
[1, 2)); rounds relax only edges whose source distance changed in the
previous round (frontier pruning), with a MIN-combine exchange.

Expressed as a :class:`~repro.core.superstep.SuperstepProgram`: the
``prepare`` hook derives the loop-invariant weight array once, outside
the driver loop, and rounds past convergence are no-ops (empty change
set relaxes nothing), so the program is safe under ``static_iters`` and
maps over batched roots for multi-source queries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import localops
from repro.core.monotone import monotone_async_program
from repro.core.partitioned import AXIS, exchange_min_int, psum_scalar
from repro.core.superstep import AsyncSuperstepProgram, SuperstepProgram

F32_INF = jnp.float32(1e30)


def edge_weight(src, dst):
    """Deterministic pseudo-random weight in [1, 2)."""
    h = (src.astype(jnp.uint32) * jnp.uint32(2654435761)
         ^ dst.astype(jnp.uint32) * jnp.uint32(40503))
    return 1.0 + (h % jnp.uint32(1 << 16)).astype(jnp.float32) / float(1 << 16)


def sssp_program(shards, max_rounds: int = 64,
                 weight_scale: float = 1.0) -> SuperstepProgram:
    """Frontier-pruned Bellman-Ford as a superstep program.

    ``weight_scale`` uniformly scales the synthesized edge weights (a
    query-time parameter for serving; 1.0 reproduces the oracle's
    weights bit-for-bit).  It must be finite and positive — the serve
    layer rejects anything else at admission (``validate_query``)
    because a NaN/Inf scale would poison every distance in a coalesced
    launch.
    """
    n, n_local = shards.n, shards.n_local
    ell_dst = shards.ell("ell_dst")

    def prepare(g):
        lo = jax.lax.axis_index(AXIS) * n_local
        g = dict(g)
        g["out_weight"] = edge_weight(g["out_src_local"] + lo,
                                      g["out_dst_global"]) \
            * jnp.float32(weight_scale)
        return g

    def init(g, root):
        lo = jax.lax.axis_index(AXIS) * n_local
        owned = (root >= lo) & (root < lo + n_local)
        at_root = owned & (jnp.arange(n_local) == root - lo)
        dist0 = jnp.where(at_root, 0.0, F32_INF)
        return dist0, at_root, jnp.int32(1)

    def step(g, state):
        dist, changed, _ = state
        srcl = g["out_src_local"]
        dst = g["out_dst_global"]
        valid = dst < n
        w = g["out_weight"]
        active = changed[srcl] & valid
        # edge relaxation = MIN-combine of candidates keyed by dst; the
        # blocked-ELL gather in localops replaces the serialized scatter
        prop = localops.scatter_combine(
            g, ell_dst, jnp.where(active, dist[srcl] + w, F32_INF), "min",
            identity=F32_INF)
        mine = exchange_min_int(prop)
        new_dist = jnp.minimum(dist, mine)
        new_changed = new_dist < dist
        cnt = psum_scalar(new_changed.sum(dtype=jnp.int32))
        return new_dist, new_changed, cnt

    def guard(g, prev, state):
        # distances non-negative and non-increasing (NaN corruption
        # fails both comparisons); change count non-negative
        dist, pdist = state[0], prev[0]
        return (dist >= 0).all() & (dist <= pdist).all() \
            & (state[2] >= 0)

    return SuperstepProgram(
        name="sssp", variant="default", inputs=("root",),
        prepare=prepare, init=init, step=step,
        halt=lambda state: state[2] <= 0,
        probe_names=("changed",), probe=lambda state: (state[2],),
        outputs=lambda state: (state[0],),
        output_names=("dist",), output_is_vertex=(True,),
        max_rounds=max_rounds, guard=guard)


def sssp_async_program(shards, max_rounds: int = 64, local_iters: int = 1,
                       weight_scale: float = 1.0) -> AsyncSuperstepProgram:
    """Async Bellman-Ford on the double-buffered exchange.

    Distance relaxation is monotone min-combine, so staleness is exact:
    a late or duplicated proposal ``dist[u] + w`` is still a valid upper
    bound and min-application can neither overshoot the true distance
    nor stick above it (every improvement is eventually delivered).
    The async run converges to the same distances as the BSP variant,
    with the halt count riding the distance exchange (the int-valued
    count is exact in the f32 payload).  The halt-count transport-dtype
    trick and the quiescence rule live in ``core/monotone.py``.
    """
    n, n_local = shards.n, shards.n_local
    ell_dst = shards.ell("ell_dst")

    def prepare(g):
        lo = jax.lax.axis_index(AXIS) * n_local
        g = dict(g)
        g["out_weight"] = edge_weight(g["out_src_local"] + lo,
                                      g["out_dst_global"]) \
            * jnp.float32(weight_scale)
        return g

    def init_vals(g, root):
        lo = jax.lax.axis_index(AXIS) * n_local
        owned = (root >= lo) & (root < lo + n_local)
        at_root = owned & (jnp.arange(n_local) == root - lo)
        return jnp.where(at_root, 0.0, F32_INF), at_root

    def relax(g, dist, frontier):
        srcl = g["out_src_local"]
        active = frontier[srcl] & (g["out_dst_global"] < n)
        return localops.scatter_combine(
            g, ell_dst,
            jnp.where(active, dist[srcl] + g["out_weight"], F32_INF),
            "min", identity=F32_INF)

    return monotone_async_program(
        name="sssp", inputs=("root",), init_vals=init_vals, relax=relax,
        outputs=lambda g, dist: (dist,), output_names=("dist",),
        output_is_vertex=(True,), n=n, n_local=n_local, inf=F32_INF,
        local_iters=local_iters, max_rounds=max_rounds, prepare=prepare)
