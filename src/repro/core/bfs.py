"""Distributed BFS: BSP baseline (PBGL-style) and the HPX-adapted
direction-optimizing implementation.

Paper mapping (SS4.1):
  * Listing 1.2 spawns an async task per remote discovery and relies on
    ``set_parent``'s compare_exchange for atomicity.  The TPU/SPMD
    adaptation aggregates all remote discoveries of a superstep into ONE
    fused exchange, and replaces CAS with an idempotent MIN-combine
    (smallest-id parent wins deterministically).
  * ``bfs/bsp``  -- level-synchronous push; every level exchanges a full
    (n,) int32 parent-proposal vector (all_to_all MIN) + a separate
    frontier-count all-reduce: the rigid-barrier BGL analogue.
  * ``bfs/fast`` -- direction-optimizing (Beamer-style push/pull chosen
    per level by frontier occupancy = the paper's runtime adaptivity),
    BIT-PACKED frontier exchange (n/32 u32 words: 32x less wire than the
    baseline), and parents derived owner-side from in-edges (no parent
    traffic at all).

The per-level LOCAL edge work routes through ``core/localops.py``: the
push-combine is ``push_combine`` (a gather through the blocked-ELL
``ell_in`` structure at parts=1, through ``ell_dst`` at parts>1) and
owner-side parent derivation is ``frontier_pull`` over ``ell_in`` (a
dense blocked-ELL gather; the Pallas BFS-pull kernel only under
``REPRO_LOCALOPS=kernel``) - no serialized scatters on any backend.
The push candidate exchange is the packed-uint32 ``exchange_or`` of
``core/partitioned.py``.

Both are expressed as :class:`~repro.core.superstep.SuperstepProgram`
factories (``init / step / halt / outputs`` over per-shard arrays); the
shared driver in core/superstep.py supplies the while/scan loop, so the
same program lowers for the 256/512-chip production meshes (see
core/dryrun.py) and vmaps over batched roots for multi-source queries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import localops
from repro.core.monotone import monotone_async_program
from repro.core.partitioned import AXIS, broadcast_global, \
    exchange_min_int, exchange_or, pack_bits, psum_scalar
from repro.core.superstep import AsyncSuperstepProgram, SuperstepProgram
from repro.obs.scopes import device_scope


INT_INF = jnp.int32(2 ** 30)


@device_scope("bfs.derive_parents")
def _derive_parents(g, ell_in, gf_packed, unvisited):
    """Owner-side parent derivation by pulling over local in-edges.

    For every local unvisited vertex, find the min-id in-neighbor that is
    in the current global frontier. Returns (new_mask, parent_prop).
    """
    prop = localops.frontier_pull(g, ell_in, gf_packed, unvisited)
    new_mask = (prop < INT_INF) & unvisited
    return new_mask, prop


def _bsp_level(g, ell_in, ell_dst, n_local, parents, frontier):
    """One BSP level: full (n,) parent-proposal exchange via a2a MIN."""
    lo = jax.lax.axis_index(AXIS) * n_local
    gid = jnp.arange(n_local, dtype=jnp.int32) + lo
    prop = localops.push_combine(
        g, ell_in, ell_dst, jnp.where(frontier, gid, INT_INF), "min",
        identity=INT_INF)
    # exchange: every partition contributes proposals for every vertex
    mine = exchange_min_int(prop)                  # (n_local,)
    unvisited = parents == INT_INF
    new_mask = (mine < INT_INF) & unvisited
    parents = jnp.where(new_mask, mine, parents)
    # separate global barrier: frontier population count
    count = psum_scalar(new_mask.sum(dtype=jnp.int32))
    return parents, new_mask, count


def _fast_level(g, ell_in, parents, gf_packed):
    """One direction-optimizing level with bit-packed exchange."""
    unvisited = parents == INT_INF
    new_mask, prop = _derive_parents(g, ell_in, gf_packed, unvisited)
    parents = jnp.where(new_mask, prop, parents)
    # pack local next frontier; all-gather the global bitmap (n/32 words)
    nf_packed_local = pack_bits(new_mask)
    gf_next = broadcast_global(nf_packed_local)
    count = psum_scalar(new_mask.sum(dtype=jnp.int32))
    return parents, gf_next, count


def _fast_level_push(g, ell_in, ell_dst, parents, frontier_local,
                     gf_packed):
    """Push variant: OR-combine candidate bits from active out-edges,
    then ship ONLY the packed candidate bitmap (n/32 u32) through the
    packed ``exchange_or``."""
    cand = localops.push_combine(g, ell_in, ell_dst, frontier_local, "or",
                                 identity=False)           # (n,) bool
    # activation bits for my slice; derive parents by pulling in-edges
    unvisited = parents == INT_INF
    activated = exchange_or(cand) & unvisited
    # parent = min in-frontier in-neighbor of activated vertices
    _, prop = _derive_parents(g, ell_in, gf_packed, activated)
    new_mask = activated & (prop < INT_INF)
    parents = jnp.where(new_mask, prop, parents)
    nf_packed_local = pack_bits(new_mask)
    gf_next = broadcast_global(nf_packed_local)
    count = psum_scalar(new_mask.sum(dtype=jnp.int32))
    return parents, new_mask, gf_next, count


def _parents_guard(count_idx: int):
    """Invariant guard shared by the BSP/fast variants: parents stay in
    ``[0, INT_INF]`` and never move once set (min-combine on unvisited
    vertices only — a parent can only go INT_INF -> id), and the
    frontier count is non-negative.  A ``-2**30`` payload corruption
    lands straight in ``parents`` and trips the lower bound."""

    def guard(g, prev, state):
        parents, pparents = state[0], prev[0]
        return (parents >= 0).all() & (parents <= pparents).all() \
            & (state[count_idx] >= 0)

    return guard


def _seed_state(root, n_local):
    """(parents0, frontier0) with only the owner's root slot set."""
    lo = jax.lax.axis_index(AXIS) * n_local
    owned = (root >= lo) & (root < lo + n_local)
    at_root = owned & (jnp.arange(n_local) == root - lo)
    parents0 = jnp.where(at_root, root,
                         jnp.full((n_local,), INT_INF, jnp.int32))
    return parents0, at_root


def bfs_bsp_program(shards, max_levels: int = 64) -> SuperstepProgram:
    """Level-synchronous BSP BFS (the rigid-barrier BGL analogue).

    Levels past convergence are natural no-ops (an empty frontier
    proposes nothing), so the program is safe under the driver's
    fixed-trip ``static_iters`` scan.
    """
    n_local = shards.n_local
    ell_in, ell_dst = shards.ell("ell_in"), shards.ell("ell_dst")

    def init(g, root):
        parents0, frontier0 = _seed_state(root, n_local)
        return parents0, frontier0, jnp.int32(1)

    def step(g, state):
        parents, frontier, _ = state
        return _bsp_level(g, ell_in, ell_dst, n_local, parents, frontier)

    return SuperstepProgram(
        name="bfs", variant="bsp", inputs=("root",),
        init=init, step=step,
        halt=lambda state: state[2] <= 0,
        outputs=lambda state: (state[0],),
        output_names=("parents",), output_is_vertex=(True,),
        max_rounds=max_levels, guard=_parents_guard(2),
        probe_names=("frontier",), probe=lambda state: (state[2],))


def bfs_fast_program(shards, max_levels: int = 64,
                     pull_threshold: float = 0.02,
                     direction: str = "adaptive") -> SuperstepProgram:
    """Direction-optimizing BFS with bit-packed frontier exchange.

    ``direction`` pins the per-level push/pull choice: ``"adaptive"``
    (the paper's runtime adaptivity, a ``lax.cond`` on frontier
    occupancy), ``"pull"``, or ``"push"``.  All three produce identical
    parents (both branches derive parents with the same min-id
    ``frontier_pull``); they differ only in work/wire per level.  Under
    ``batch=B`` vmapping the per-lane cond degenerates to running BOTH
    branches and selecting, so batched builds default to ``"pull"``
    via the registry's ``batch_defaults`` (4-12x per-query throughput
    at serving bucket sizes).
    """
    n, n_local = shards.n, shards.n_local
    ell_in = shards.ell("ell_in")
    ell_dst = shards.ell("ell_dst")
    thresh = jnp.int32(max(1, int(n * pull_threshold)))
    if direction not in ("adaptive", "pull", "push"):
        raise ValueError(f"direction must be adaptive|pull|push, "
                         f"got {direction!r}")

    def init(g, root):
        parents0, frontier0 = _seed_state(root, n_local)
        gf0 = broadcast_global(pack_bits(frontier0))
        return parents0, frontier0, gf0, jnp.int32(1)

    def step(g, state):
        parents, frontier, gf, count = state

        @device_scope("bfs.push")
        def push(_):
            p, f, g2, c = _fast_level_push(g, ell_in, ell_dst, parents,
                                           frontier, gf)
            return p, f, g2, c

        @device_scope("bfs.pull")
        def pull(_):
            p, g2, c = _fast_level(g, ell_in, parents, gf)
            # recover local frontier from my slice of the packed bitmap
            lo_w = jax.lax.axis_index(AXIS) * (n_local // 32)
            words = jax.lax.dynamic_slice_in_dim(g2, lo_w, n_local // 32)
            f = ((words[jnp.arange(n_local) >> 5]
                  >> (jnp.arange(n_local) & 31).astype(jnp.uint32)) & 1
                 ).astype(bool)
            return p, f, g2, c

        if direction == "pull":
            return pull(None)
        if direction == "push":
            return push(None)
        return jax.lax.cond(count < thresh, push, pull, operand=None)

    return SuperstepProgram(
        name="bfs", variant="fast", inputs=("root",),
        init=init, step=step,
        halt=lambda state: state[3] <= 0,
        outputs=lambda state: (state[0],),
        output_names=("parents",), output_is_vertex=(True,),
        max_rounds=max_levels, guard=_parents_guard(3),
        probe_names=("frontier",), probe=lambda state: (state[3],))


def bfs_async_program(shards, max_levels: int = 64,
                      local_iters: int = 1) -> AsyncSuperstepProgram:
    """Async BFS on the double-buffered exchange.

    Per-level parent proposals don't survive staleness (a stale frontier
    can propose a parent one level too deep), so the async variant runs
    the stale-safe formulation instead: LEVELS via monotone min-combine
    (unit-weight SSSP — level k+1's relaxations overlap level k's
    in-flight exchange, and late/duplicate proposals are no-ops under
    min), with the halt count piggybacked on the level exchange itself —
    no separate psum collective per level, which is the fused
    halt-reduction this variant exists to demonstrate.  Parents are then
    derived AFTER convergence in one ``pull_min_eq`` pass over in-edges
    (min-id in-neighbor one level up), reproducing the BSP variants'
    deterministic min-id parent rule from exact levels.
    """
    n, n_local = shards.n, shards.n_local
    ell_in = shards.ell("ell_in")
    ell_dst = shards.ell("ell_dst")

    def init_vals(g, root):
        parents0, at_root = _seed_state(root, n_local)
        level0 = jnp.where(at_root, 0, INT_INF)
        return level0, at_root

    def relax(g, level, frontier):
        return localops.push_combine(
            g, ell_in, ell_dst, jnp.where(frontier, level + 1, INT_INF),
            "min", identity=INT_INF)

    def outputs(g, level):
        lvl_global = broadcast_global(level)
        # parent of v = min-id in-neighbor exactly one level up; the
        # root (level 0) is its own parent, unreached rows stay INT_INF
        # (their target INT_INF - 1 matches no real level)
        prop = localops.pull_min_eq(g, ell_in, lvl_global, level - 1)
        lo = jax.lax.axis_index(AXIS) * n_local
        gid = jnp.arange(n_local, dtype=jnp.int32) + lo
        return (jnp.where(level == 0, gid, prop),)

    return monotone_async_program(
        name="bfs", inputs=("root",), init_vals=init_vals, relax=relax,
        outputs=outputs, output_names=("parents",),
        output_is_vertex=(True,), n=n, n_local=n_local, inf=INT_INF,
        local_iters=local_iters, max_rounds=max_levels)
