"""Distributed PageRank: BSP baseline (BGL-style) and the HPX-adapted
optimized implementation.

Paper mapping (SS4.2) - the three phases per iteration:
  1. Contribution accumulation: contrib[i] = rank[i] / out_degree[i];
     local neighbors applied directly, remote ones shipped to the owner.
  2. Rank update: rank[i] = base + alpha * z.
  3. Error computation: sum |rank_new - rank_old| (convergence).

Both variants are :class:`~repro.core.superstep.SuperstepProgram`
factories; the shared driver in core/superstep.py owns the while/scan
loop.

``pagerank/bsp``  -- pull over in-edges after ALL-GATHERING the full (n,)
    f32 contribution vector every iteration (the ghost-replication
    pattern of distributed BGL), plus a separate error all-reduce.
``pagerank/fast`` -- push-aggregate: each partition segment-sums its
    local edges' contributions into a length-n accumulator and ONE fused
    reduce-scatter delivers owner slices (the paper's "remote
    contribution applied atomically at the owner", batched).  The
    exchange payload is quantized bf16 with an error-feedback residual
    (2x less wire); the error term rides the same collective schedule.

The local segment-sum is the SpMV hot spot; it routes through
``core/localops.py`` (``spmv_pull`` over the blocked-ELL in-neighbor
lists for the pull variant, ``push_combine`` for the push variants:
over the same ``ell_in`` lists at parts=1, through ``ell_dst`` at
parts>1): a dense per-bucket gather + row-sum on every backend (the
Pallas SpMV kernel only under ``REPRO_LOCALOPS=kernel``) - the
serialized COO scatter survives only as the ``REPRO_LOCALOPS=ref``
debug path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import localops
from repro.core.partitioned import AXIS, broadcast_global, exchange_sum, \
    exchange_sum_finish, exchange_sum_start, psum_scalar
from repro.core.superstep import AsyncSuperstepProgram, SuperstepProgram
from repro.obs.scopes import device_scope


ALPHA = 0.85


def _local_contrib(rank, out_degree):
    return jnp.where(out_degree > 0, rank / out_degree.astype(jnp.float32),
                     0.0)


def _rank_mass_ok(rank, n, n_orig, margin):
    """Mass-conservation invariant for the fault guards.

    Rank mass starts at ``n / n_orig`` (padded tail vertices carry an
    initial 1/n_orig in the unseeded variants) and only shrinks toward
    the dangling-adjusted fixed point >= (1 - alpha), so any round's
    global mass must sit in ``((1 - alpha) * 0.9, n/n_orig * margin)``.
    ``margin`` absorbs transient overshoot (bf16 error feedback, stale
    remote snapshots); a dropped/duplicated/corrupted contribution
    block moves mass outside the band, and NaN fails the element-wise
    non-negativity check.
    """
    mass = psum_scalar(rank.sum())
    cap = (1.0 + (n - n_orig) / n_orig) * margin
    return (rank >= 0).all() & (mass > (1.0 - ALPHA) * 0.9) & (mass < cap)


def pagerank_bsp_program(shards, iters: int = 50,
                         tol: float = 1e-6) -> SuperstepProgram:
    """BGL-style pull PageRank (ghost replication via all-gather)."""
    n, n_local, n_orig = shards.n, shards.n_local, shards.n_orig
    ell_in = shards.ell("ell_in")
    base = (1.0 - ALPHA) / n_orig

    def init(g, *_):
        rank0 = jnp.full((n_local,), 1.0 / n_orig, jnp.float32)
        return rank0, jnp.float32(1.0)

    def step(g, state):
        rank, _ = state
        contrib = _local_contrib(rank, g["out_degree"])
        cg = broadcast_global(contrib)              # all-gather (n,) f32
        z = localops.spmv_pull(g, ell_in, cg)       # local SpMV (pull)
        new_rank = base + ALPHA * z
        err = psum_scalar(jnp.abs(new_rank - rank).sum())  # extra barrier
        return new_rank, err

    def guard(g, prev, state):
        rank, err = state
        return _rank_mass_ok(rank, n, n_orig, 1.02) & (err >= 0)

    return SuperstepProgram(
        name="pagerank", variant="bsp", inputs=(),
        init=init, step=step,
        halt=lambda state: state[1] <= tol,
        probe_names=("err",), probe=lambda state: (state[1],),
        outputs=lambda state: (state[0], state[1]),
        output_names=("rank", "err"), output_is_vertex=(True, False),
        max_rounds=iters, guard=guard)


def pagerank_fast_program(shards, iters: int = 50,
                          tol: float = 1e-6, compress=True,
                          switch_factor: float = 1e3,
                          err_every: int = 5,
                          seeded: bool = False) -> SuperstepProgram:
    """Push-aggregate PageRank with fused reduce-scatter exchange and
    ADAPTIVE bf16 error-feedback compression.

    While the iteration error is far from tol, the exchange ships bf16
    (2x less wire, error-feedback residual keeps the average unbiased);
    once err < switch_factor * tol the loop switches to fp32 payloads so
    convergence reaches the exact fixed point.  Runtime adaptivity in the
    spirit of the paper's adaptive_core_chunk_size executor.

    The convergence check (a global barrier) runs every ``err_every``
    iterations instead of every iteration - the BSP baseline's
    per-iteration error all-reduce is exactly the synchronization cost
    the paper calls out; batching it removes 80% of the barriers at the
    cost of up to err_every-1 extra (cheap) iterations.  The iteration
    counter rides in the program state (not the driver) because
    ``err_every`` is an algorithm policy, not loop control.

    With ``seeded=True`` the program becomes the ``pagerank/warm``
    variant: init adopts a per-vertex ``rank0`` input (typically the
    previous snapshot epoch's rank vector).  Power iteration is a
    contraction to ONE fixed point, so any seed is exact at
    convergence — a near-fixed-point seed just reaches tol in far
    fewer rounds (the dynamic-graph warm-restart win).
    """
    n, n_local, n_orig = shards.n, shards.n_local, shards.n_orig
    ell_in, ell_dst = shards.ell("ell_in"), shards.ell("ell_dst")
    base = (1.0 - ALPHA) / n_orig

    def init(g, *inputs):
        if seeded:
            (rank_in,) = inputs
            lo = jax.lax.axis_index(AXIS) * n_local
            gid = jnp.arange(n_local, dtype=jnp.int32) + lo
            # padded tail vertices are edgeless and never gathered:
            # zero them so the seed's value there is irrelevant
            rank0 = jnp.where(gid < n_orig, rank_in.astype(jnp.float32), 0.0)
        else:
            rank0 = jnp.full((n_local,), 1.0 / n_orig, jnp.float32)
        resid0 = jnp.zeros((n,), jnp.float32)
        return rank0, resid0, jnp.float32(1.0), jnp.int32(0)

    def step(g, state):
        rank, resid, err_prev, it = state
        contrib = _local_contrib(rank, g["out_degree"])
        # local segment-sum into a length-n accumulator (SpMV push);
        # localops routes it to a dense blocked-ELL gather + row-sum.
        acc = localops.push_combine(g, ell_in, ell_dst, contrib, "add",
                                    identity=jnp.float32(0.0))

        @device_scope("pagerank.compressed")
        def compressed(_):
            # error-feedback quantization: ship bf16, keep the residual
            payload = (acc + resid).astype(jnp.bfloat16)
            new_resid = (acc + resid) - payload.astype(jnp.float32)
            return exchange_sum(payload).astype(jnp.float32), new_resid

        @device_scope("pagerank.exact")
        def exact(_):
            return exchange_sum(acc + resid), jnp.zeros_like(resid)

        if compress == "always":
            # static variant (dry-run/roofline): no precision switch
            z, new_resid = compressed(None)
        elif compress:
            # switch no later than the bf16 noise floor (sum|delta| ~ 3e-3
            # for rank mass 1), else a tight tol would never leave the
            # compressed regime
            switch_at = jnp.maximum(switch_factor * tol, 3e-3)
            z, new_resid = jax.lax.cond(
                err_prev > switch_at, compressed, exact, operand=None)
        else:
            z, new_resid = exact(None)
        new_rank = base + ALPHA * z
        err = jax.lax.cond(
            (it + 1) % err_every == 0,
            lambda _: psum_scalar(jnp.abs(new_rank - rank).sum()),
            lambda _: err_prev,
            operand=None)
        return new_rank, new_resid, err, it + 1

    def guard(g, prev, state):
        rank, resid, err, it = state
        return _rank_mass_ok(rank, n, n_orig, 1.02) \
            & jnp.isfinite(resid).all() & (err >= 0) & (it >= 0)

    return SuperstepProgram(
        name="pagerank", variant="warm" if seeded else "fast",
        inputs=("rank0",) if seeded else (),
        init=init, step=step,
        halt=lambda state: state[2] <= tol,
        probe_names=("err",), probe=lambda state: (state[2],),
        outputs=lambda state: (state[0], state[2]),
        output_names=("rank", "err"), output_is_vertex=(True, False),
        max_rounds=iters, guard=guard)


def pagerank_async_program(shards, iters: int = 64, tol: float = 1e-6,
                           staleness: int = 1) -> AsyncSuperstepProgram:
    """Bounded-staleness push PageRank on the double-buffered exchange.

    The rank update splits into an own-partition term (always fresh —
    computed in the overlap window every round) and a remote term
    (delivered by the in-flight reduce-scatter): each round runs
    ``rank = base + alpha * (own + remote_snapshot)``, and the remote
    snapshot refreshes only every ``staleness`` rounds — the bounded-
    staleness knob.  Between refreshes NO collective runs at all (wire
    per round drops by the same factor); at a refresh the exchange that
    has been in flight since the previous one is finished and the next
    is started, with the local residual ``sum |delta rank|`` piggybacked
    as the payload's trailing column so convergence detection never pays
    a separate psum barrier.

    Staleness is BOUNDED, not best-effort: the remote term used in any
    round derives from ranks at most ``2 * staleness + 1`` rounds old
    (shipped <= staleness rounds after they were computed, then served
    for <= staleness rounds).  The program tracks the realized maximum
    and reports it as the ``max_age`` output, which the conformance
    lane asserts against that bound.  Power iteration is an alpha-
    contraction with ONE fixed point, so the stale recurrence
    ``e(k+1) <= alpha * max(e(k), ..., e(k - 2*staleness - 1))`` still
    converges to the exact BSP answer — per-round error may oscillate,
    but its max over windows of ``2*staleness + 2`` rounds (delay bound
    + 1) is monotone non-increasing (the property suite pins this on
    the NumPy model of the recurrence).
    """
    n, n_local, n_orig = shards.n, shards.n_local, shards.n_orig
    ell_in, ell_dst = shards.ell("ell_in"), shards.ell("ell_dst")
    base = (1.0 - ALPHA) / n_orig
    if staleness < 1:
        raise ValueError(f"staleness must be >= 1, got {staleness}")

    def _contrib_acc(g, rank):
        """(n,) push accumulator with the OWN slice zeroed for shipping:
        the exchange must deliver purely-remote contributions."""
        contrib = _local_contrib(rank, g["out_degree"])
        acc = localops.push_combine(g, ell_in, ell_dst, contrib, "add",
                                    identity=jnp.float32(0.0))
        lo = jax.lax.axis_index(AXIS) * n_local
        own = jax.lax.dynamic_slice_in_dim(acc, lo, n_local)
        ship = jax.lax.dynamic_update_slice_in_dim(
            acc, jnp.zeros((n_local,), jnp.float32), lo, axis=0)
        return own, ship

    def init(g):
        rank0 = jnp.full((n_local,), 1.0 / n_orig, jnp.float32)
        _, ship0 = _contrib_acc(g, rank0)
        # the err column ships 1.0 per partition so halt can't fire
        # before a real residual arrives
        handle0 = exchange_sum_start(ship0, jnp.float32(1.0))
        state0 = (rank0, jnp.zeros((n_local,), jnp.float32), ship0,
                  jnp.float32(1.0), jnp.float32(1.0), jnp.int32(0),
                  jnp.int32(1), jnp.int32(1), jnp.int32(1))
        return state0, handle0

    def local(g, state):
        rank, remote, _, _, err_g, it, age_cur, age_infl, max_age = state
        own, ship = _contrib_acc(g, rank)
        new_rank = base + ALPHA * (own + remote)
        err_local = jnp.abs(new_rank - rank).sum()
        max_age = jnp.maximum(max_age, age_cur)
        return (new_rank, remote, ship, err_local, err_g, it,
                age_cur, age_infl, max_age)

    def fold(g, state, handle):
        (rank, remote, ship, err_local, err_g, it,
         age_cur, age_infl, max_age) = state

        def refresh(_):
            remote_new, err_glob = exchange_sum_finish(handle)
            new_handle = exchange_sum_start(ship, err_local)
            # delivered snapshot: shipped age_infl rounds of aging ago,
            # +1 for this round; the fresh payload is 1 round old
            return (remote_new, err_glob, new_handle,
                    age_infl + jnp.int32(1), jnp.int32(1))

        def keep(_):
            return (remote, err_g, handle,
                    age_cur + jnp.int32(1), age_infl + jnp.int32(1))

        remote, err_g, handle, age_cur, age_infl = jax.lax.cond(
            it % staleness == 0, refresh, keep, operand=None)
        state = (rank, remote, ship, err_local, err_g, it + 1,
                 age_cur, age_infl, max_age)
        return state, handle

    def guard(g, prev, state):
        # looser mass margin: the remote snapshot lags the local term by
        # up to 2*staleness+1 rounds, so transient overshoot is larger
        rank, remote, ship = state[0], state[1], state[2]
        return _rank_mass_ok(rank, n, n_orig, 1.05) \
            & jnp.isfinite(remote).all() & (remote >= 0).all() \
            & jnp.isfinite(ship).all() & (ship >= 0).all() \
            & (state[3] >= 0) & (state[4] >= 0) \
            & (state[6] >= 0) & (state[7] >= 0) & (state[8] >= 0)

    return AsyncSuperstepProgram(
        name="pagerank", variant="async", inputs=(),
        init=init, local=local, fold=fold,
        halt=lambda state: state[4] <= tol,
        probe_names=("err",), probe=lambda state: (state[4],),
        outputs=lambda g, state: (state[0], state[4], state[8]),
        output_names=("rank", "err", "max_age"),
        output_is_vertex=(True, False, False),
        max_rounds=iters, guard=guard)
