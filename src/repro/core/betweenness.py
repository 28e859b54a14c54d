"""Distributed betweenness centrality (Brandes) — the engine's first
MULTI-PHASE superstep program.

Brandes decomposes per-source betweenness into (1) a forward BFS that
counts shortest paths (sigma) while recording distance levels, then (2)
a backward dependency-accumulation sweep over the shortest-path DAG.
Phase (2) needs phase (1)'s outputs as its initial state, which is
exactly what :class:`~repro.core.superstep.PhasedProgram` /
``run_phases`` provide: the forward program's ``(dist, sigma)`` outputs
thread into the backward program's ``init``.

Semantics: single-source dependencies ``delta_s(v)`` on the DIRECTED
MULTIGRAPH underlying the edge list (parallel edges are parallel
shortest paths), unweighted, with the conventional ``delta_s(s) = 0``.
Summing the output over a batch of sources (``batch=B`` reuses
``run_program_batched`` — B forward sweeps share one graph residency)
yields sampled approximate betweenness; all n sources is the exact
score.

Forward pass: per level, frontier vertices push ``sigma`` along
out-edges into a length-n accumulator; ONE fused ``exchange_sum``
delivers owner slices; receivers that were unvisited adopt the level
and the path-count sum (all shortest-path predecessors of a level-L
vertex are, by level-synchrony, in the level-(L-1) frontier, so sigma
arrives complete in one superstep).

Backward sweep: rather than walking levels down with a counter, each
superstep recomputes the whole dependency relaxation

    delta(v) = sigma(v) * sum_{v->w, dist(w)=dist(v)+1}
                          (1 + delta(w)) / sigma(w)

from the current delta (one all-gather of the (n,) coefficient vector
per superstep, the pull-mode pattern of ``pagerank/bsp``).  Values
propagate up one level per superstep, so the sweep converges in
max-level rounds to the exact Brandes fixed point; further rounds
recompute bit-identical values, making the phase idempotent — halt on
zero changed entries, and safe under ``static_iters``.

sigma/delta arithmetic is f32; sigma values are integers (exact below
2^24), so conformance against the NumPy oracle is tight.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import localops
from repro.core.partitioned import AXIS, broadcast_global, exchange_sum, \
    psum_scalar
from repro.core.superstep import PhasedProgram, SuperstepProgram

INT_INF = jnp.int32(2 ** 30)


def bc_forward_program(shards, max_levels: int = 64) -> SuperstepProgram:
    """Phase 1: level-synchronous BFS counting shortest paths."""
    n_local = shards.n_local
    ell_in, ell_dst = shards.ell("ell_in"), shards.ell("ell_dst")

    def init(g, root):
        lo = jax.lax.axis_index(AXIS) * n_local
        at_root = (root >= lo) & (root < lo + n_local) & \
            (jnp.arange(n_local) == root - lo)
        dist0 = jnp.where(at_root, 0, INT_INF)
        sigma0 = jnp.where(at_root, 1.0, 0.0)
        return dist0, sigma0, at_root, jnp.int32(1), jnp.int32(1)

    def step(g, state):
        dist, sigma, frontier, level, _ = state
        acc = localops.push_combine(
            g, ell_in, ell_dst, jnp.where(frontier, sigma, 0.0), "add",
            identity=jnp.float32(0.0))
        recv = exchange_sum(acc)                    # (n_local,) f32
        newly = (recv > 0) & (dist == INT_INF)
        dist = jnp.where(newly, level, dist)
        sigma = sigma + jnp.where(newly, recv, 0.0)
        cnt = psum_scalar(newly.sum(dtype=jnp.int32))
        return dist, sigma, newly, level + 1, cnt

    def guard(g, prev, state):
        # forward invariants: levels adopt once (non-increasing from
        # INT_INF), path counts finite / non-negative / non-decreasing
        dist, sigma, _, level, cnt = state
        return (dist >= 0).all() & (dist <= prev[0]).all() \
            & jnp.isfinite(sigma).all() & (sigma >= prev[1]).all() \
            & (level >= prev[3]) & (cnt >= 0)

    return SuperstepProgram(
        name="betweenness", variant="forward", inputs=("root",),
        init=init, step=step,
        halt=lambda state: state[4] <= 0,
        outputs=lambda state: (state[0], state[1]),
        output_names=("dist", "sigma"), output_is_vertex=(True, True),
        max_rounds=max_levels, guard=guard)


def bc_backward_program(shards, max_levels: int = 64) -> SuperstepProgram:
    """Phase 2: dependency accumulation over the shortest-path DAG.

    ``init`` receives the forward phase's (dist, sigma) — the phase
    chaining contract.
    """
    n, n_local = shards.n, shards.n_local
    ell_out = shards.ell("ell_out")

    def init(g, dist, sigma):
        delta0 = jnp.zeros((n_local,), jnp.float32)
        dist_g = broadcast_global(dist)             # loop-invariant (n,)
        return delta0, dist, sigma, dist_g, jnp.int32(1)

    def step(g, state):
        delta, dist, sigma, dist_g, _ = state
        coef = jnp.where(sigma > 0, (1.0 + delta) / jnp.maximum(sigma, 1.0),
                         0.0)
        coef_g = broadcast_global(coef)             # (n,) pull replica
        srcl, dst = g["out_src_local"], g["out_dst_global"]
        valid = dst < n
        safe_dst = jnp.where(valid, dst, 0)
        deeper = valid & (dist_g[safe_dst] == dist[srcl] + 1)
        contrib = jnp.where(deeper, coef_g[safe_dst], 0.0)
        s = localops.scatter_combine(g, ell_out, contrib, "add",
                                     identity=jnp.float32(0.0))
        new_delta = sigma * s
        changed = psum_scalar((new_delta != delta).sum(dtype=jnp.int32))
        return new_delta, dist, sigma, dist_g, changed

    def outputs(state):
        delta, dist, sigma, _, _ = state
        bc = jnp.where(dist == 0, 0.0, delta)       # delta_s(s) := 0
        return bc, sigma, dist

    def guard(g, prev, state):
        # dependency accumulation is a sum of non-negative coefficient
        # terms: finite and non-negative (a NaN coefficient broadcast
        # lands in delta unfiltered); the frozen forward fields must
        # stay bit-frozen
        delta, dist, sigma, _, changed = state
        return jnp.isfinite(delta).all() & (delta >= 0).all() \
            & (dist == prev[1]).all() & (sigma == prev[2]).all() \
            & (changed >= 0)

    return SuperstepProgram(
        name="betweenness", variant="backward", inputs=(),
        init=init, step=step,
        halt=lambda state: state[4] <= 0,
        outputs=outputs,
        output_names=("bc", "sigma", "dist"),
        output_is_vertex=(True, True, True),
        max_rounds=max_levels, guard=guard)


def betweenness_program(shards, max_levels: int = 64) -> PhasedProgram:
    """Forward + backward Brandes as ONE phased program."""
    return PhasedProgram(
        name="betweenness", variant="default", inputs=("root",),
        phases=(bc_forward_program(shards, max_levels),
                bc_backward_program(shards, max_levels)),
        output_names=("bc", "sigma", "dist"),
        output_is_vertex=(True, True, True))
