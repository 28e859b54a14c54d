"""Distributed connected components (label propagation / Shiloach-Vishkin
style hooking) - another paper "future work" algorithm.

Treats the graph as undirected by propagating labels along BOTH edge
directions; converges when no label changes.  Expressed as a
:class:`~repro.core.superstep.SuperstepProgram`; rounds past
convergence are no-ops (labels are already fixed points of min-combine),
so the program is safe under the driver's ``static_iters`` scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import localops
from repro.core.monotone import monotone_async_program
from repro.core.partitioned import AXIS, exchange_min_int, psum_scalar
from repro.core.superstep import AsyncSuperstepProgram, SuperstepProgram

INT_INF = jnp.int32(2 ** 30)


def cc_program(shards, max_rounds: int = 64,
               seeded: bool = False) -> SuperstepProgram:
    """Label propagation over both edge directions as a superstep program.

    With ``seeded=True`` the program becomes the ``cc/incremental``
    variant: init adopts a per-vertex ``labels0`` input instead of the
    identity labeling.  Min-propagation converges to
    ``min over u in component(v) of labels0[u]``, so a warm seed from a
    previous epoch is EXACT as long as every mutation since only ADDED
    edges (components only merge, and each old component carries its
    minimum vertex id on all members); the identity seed reproduces the
    cold start bit-for-bit.
    """
    n, n_local = shards.n, shards.n_local
    n_orig = shards.n_orig
    ell_in, ell_dst = shards.ell("ell_in"), shards.ell("ell_dst")
    ell_src = shards.ell("ell_src")

    def init(g, *inputs):
        lo = jax.lax.axis_index(AXIS) * n_local
        gid = jnp.arange(n_local, dtype=jnp.int32) + lo
        if seeded:
            (labels0,) = inputs
            # padded tail vertices are edgeless: keep their identity
            # labels so they stay inert fixed points
            labels0 = jnp.where(gid < n_orig, labels0.astype(jnp.int32), gid)
        else:
            labels0 = gid
        return labels0, jnp.int32(1)

    def step(g, state):
        labels, _ = state
        in_src = g["in_src_global"]
        in_dstl = g["in_dst_local"]
        in_valid = in_src < n
        # propose my label to out-neighbors (push direction); the local
        # MIN-combine is a blocked-ELL gather+reduce (localops)
        prop = localops.push_combine(g, ell_in, ell_dst, labels, "min",
                                     identity=INT_INF)
        mine = exchange_min_int(prop)
        new_labels = jnp.minimum(labels, mine)
        # pull direction: adopt min label of in-neighbors (needs their
        # labels -> ship proposals keyed by in-edge source owner)
        prop2 = localops.scatter_combine(
            g, ell_src, jnp.where(in_valid, new_labels[in_dstl], INT_INF),
            "min", identity=INT_INF)
        mine2 = exchange_min_int(prop2)
        new_labels = jnp.minimum(new_labels, mine2)
        cnt = psum_scalar((new_labels < labels).sum(dtype=jnp.int32))
        return new_labels, cnt

    def guard(g, prev, state):
        # min-propagation invariants: labels non-negative and
        # non-increasing; change count non-negative
        labels, plabels = state[0], prev[0]
        return (labels >= 0).all() & (labels <= plabels).all() \
            & (state[1] >= 0)

    return SuperstepProgram(
        name="cc", variant="incremental" if seeded else "default",
        inputs=("labels0",) if seeded else (),
        init=init, step=step,
        halt=lambda state: state[1] <= 0,
        probe_names=("changed",), probe=lambda state: (state[1],),
        outputs=lambda state: (state[0],),
        output_names=("labels",), output_is_vertex=(True,),
        max_rounds=max_rounds, guard=guard)


def cc_async_program(shards, max_rounds: int = 64,
                     local_iters: int = 1) -> AsyncSuperstepProgram:
    """Async label propagation on the double-buffered exchange.

    Min-label propagation is the textbook stale-safe monotone program:
    labels only decrease, min-combine is idempotent and commutative, so
    applying a stale or duplicated proposal can never produce a wrong
    label — the async run converges to the BIT-identical fixed point
    (min vertex id per component) the BSP variant reaches.  Both edge
    directions propose into ONE shared (n,) accumulator (a label
    proposal is addressed to a global vertex id either way), so one
    exchange per round carries push + pull + the piggybacked halt count.
    """
    n, n_local = shards.n, shards.n_local

    def init_vals(g):
        lo = jax.lax.axis_index(AXIS) * n_local
        gid = jnp.arange(n_local, dtype=jnp.int32) + lo
        # every vertex proposes its identity label in round one
        return gid, jnp.ones((n_local,), bool)

    def relax(g, labels, frontier):
        in_dstl = g["in_dst_local"]
        in_valid = g["in_src_global"] < n
        push = localops.push_combine(
            g, shards.ell("ell_in"), shards.ell("ell_dst"),
            jnp.where(frontier, labels, INT_INF), "min", identity=INT_INF)
        pull = localops.scatter_combine(
            g, shards.ell("ell_src"),
            jnp.where(frontier[in_dstl] & in_valid, labels[in_dstl],
                      INT_INF),
            "min", identity=INT_INF)
        return jnp.minimum(push, pull)

    return monotone_async_program(
        name="cc", inputs=(), init_vals=init_vals, relax=relax,
        outputs=lambda g, labels: (labels,), output_names=("labels",),
        output_is_vertex=(True,), n=n, n_local=n_local, inf=INT_INF,
        local_iters=local_iters, max_rounds=max_rounds)
