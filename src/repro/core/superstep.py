"""Superstep programs: the engine's declarative algorithm abstraction.

"The Anatomy of Large-Scale Distributed Graph Algorithms" (Firoz et al.)
decomposes distributed graph algorithms into reusable runtime pieces —
a work bundle (what one superstep does), an ordering/termination policy,
and a synchronization strategy.  This module makes that decomposition
the public API: an algorithm is a :class:`SuperstepProgram` (pure
``init / step / halt / outputs`` callables over per-partition graph
arrays + the ``partitioned.py`` exchange primitives), and ONE shared
driver (:func:`run_program`) supplies the loop machinery every
hand-rolled driver used to duplicate:

  * early-exit ``lax.while_loop`` when termination is data-dependent
    (the production path),
  * fixed-trip ``lax.scan`` when ``static_iters > 0`` (the dry-run /
    roofline path: static trip counts make the cost model exact; steps
    past convergence are natural no-ops by construction), and
  * round accounting (the returned round count is driver state, not
    program state).

Programs never call collectives for loop control themselves — ``halt``
reads a count/error scalar the step already reduced — so swapping the
driver (BSP scan vs early-exit, single- vs multi-source) never touches
algorithm code.  All callables run INSIDE ``shard_map`` over the
1-D "parts" axis; ``core/api.py`` owns the jit/shard_map wrapping and
the compile cache.

The drivers name their device work for a profiler trace
(``obs/scopes.py``): ``superstep.init``, ``superstep.loop`` (the loop
and what XLA adds to it), inside it ``superstep.step`` (a round, with
its round count) and ``superstep.halt`` (the loop condition),
``superstep.guard`` and ``superstep.telemetry`` where compiled in, and
``superstep.outputs``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core.partitioned import AXIS, psum_scalar
from repro.obs import telemetry as obs_tel
from repro.obs.scopes import device_scope


@dataclass(frozen=True)
class SuperstepProgram:
    """A distributed graph algorithm as data.

    The per-shard callables (all traced inside ``shard_map``):

      prepare(g) -> g        optional: derive loop-invariant edge data
                             (e.g. SSSP weights) once, outside the loop
      init(g, *inputs) -> state
                             build the initial state pytree from the
                             per-query inputs (e.g. a root vertex)
      step(g, state) -> state
                             ONE superstep: local compute + exchange;
                             must fold any convergence scalar (frontier
                             count, residual error) into the state
      halt(state) -> bool    True when converged (driver also stops at
                             ``max_rounds``); ignored under static_iters
      outputs(state) -> tuple
                             final per-shard outputs, aligned with
                             ``output_names`` / ``output_is_vertex``
      guard(g, prev, state) -> bool
                             optional per-round invariant check (local
                             per-shard verdict; the driver makes it
                             uniform): True = the round's state is
                             consistent with the algorithm's invariants
                             (monotone non-increase, mass conservation,
                             non-negativity).  ``None`` falls back to
                             the NaN/Inf screen over float state leaves.
                             Compiled in only under ``guard=True`` runs.
      probe(state) -> tuple  optional telemetry probes, aligned with
                             ``probe_names``: globally-uniform scalars
                             (frontier size, residual — values the step
                             already reduced) recorded per round into
                             the telemetry series.  Compiled in only
                             under ``telemetry=True`` runs.
    """

    name: str
    variant: str
    inputs: tuple[str, ...]           # per-query input names, e.g. ("root",)
    init: Callable[..., Any]
    step: Callable[[dict, Any], Any]
    halt: Callable[[Any], Any]
    outputs: Callable[[Any], tuple]
    output_names: tuple[str, ...]
    output_is_vertex: tuple[bool, ...]  # True: (n_local,) field -> sharded
    max_rounds: int = 64
    prepare: Callable[[dict], dict] = field(default=lambda g: g)
    guard: Callable[[dict, Any, Any], Any] | None = None
    probe_names: tuple[str, ...] = ()
    probe: Callable[[Any], tuple] | None = None

    @property
    def key(self) -> str:
        return f"{self.name}/{self.variant}"


# Documented rounds slack for async vs BSP runs of the SAME monotone
# program: fold() relaxes delivered updates before re-shipping, so a
# cross-partition hop still costs one round (BSP parity) and the local
# closure only adds progress — the overhead is pipeline fill plus the
# two-quiescent-rounds halt rule.  tests/test_async.py and the
# benchmarks/compare.py rounds gate both read these.
ASYNC_ROUNDS_SLACK_FACTOR = 1.5
ASYNC_ROUNDS_SLACK_CONST = 4


@dataclass(frozen=True)
class AsyncSuperstepProgram:
    """A stale-tolerant algorithm for the double-buffered driver.

    Where :class:`SuperstepProgram.step` blocks on a full exchange every
    round (the BSP barrier), an async program splits one round into:

      init(g, *inputs) -> (state, handle)
                             seed the state AND issue the first exchange
                             (``partitioned.exchange_*_start``) so round
                             one has an in-flight handle to finish
      local(g, state) -> state
                             the overlap window: compute on already-
                             resident data only — NO collectives here;
                             this work hides the in-flight exchange
      fold(g, state, handle) -> (state, handle)
                             finish the handle (pure local reduction),
                             apply the delivered updates, and start the
                             next exchange
      halt(state) -> bool    must read only globally-uniform values (the
                             piggybacked scalar a finish returned) — all
                             partitions run the same trip count
      outputs(g, state) -> tuple
                             post-loop finalization; unlike the BSP form
                             it receives ``g`` (and MAY use collectives:
                             it runs outside the loop, uniformly)

    The driver calls ``local`` then ``fold`` each round, so the exchange
    started in round k's ``fold`` crosses the loop carry and is consumed
    after round k+1's ``local`` — local compute and wire movement
    overlap, which is the HPX insight the source paper's follow-up names
    as the fix for latency-bound BSP scaling.
    """

    name: str
    variant: str
    inputs: tuple[str, ...]
    init: Callable[..., Any]
    local: Callable[[dict, Any], Any]
    fold: Callable[[dict, Any, Any], Any]
    halt: Callable[[Any], Any]
    outputs: Callable[[dict, Any], tuple]
    output_names: tuple[str, ...]
    output_is_vertex: tuple[bool, ...]
    max_rounds: int = 64
    prepare: Callable[[dict], dict] = field(default=lambda g: g)
    guard: Callable[[dict, Any, Any], Any] | None = None
    probe_names: tuple[str, ...] = ()
    probe: Callable[[Any], tuple] | None = None

    @property
    def key(self) -> str:
        return f"{self.name}/{self.variant}"


# --------------------------------------------------------------------------
# Telemetry series.  Under ``telemetry=True`` the while-loop drivers
# append a zero-initialised ``(max_rounds, 2 + len(probe_names))`` f32
# buffer to the carry and write one row per executed round:
#
#     [done, halted, *probes]
#
# ``done`` = 1.0 marks rows a round actually wrote — round counts are
# only known on device, so the host (obs.telemetry.PhaseSeries) trims on
# this column; it is also what lets a PhasedProgram concatenate phase
# buffers (zero gaps between phases are simply invalid rows).  ``halted``
# is the halt predicate evaluated on the round's resulting state;
# ``probes`` are the program's declared globally-uniform scalars.  The
# telemetry-off path carries ``()`` in the series slot, which adds no
# leaves to the traced loop — outputs stay bit-identical.
# --------------------------------------------------------------------------


@device_scope("superstep.telemetry")
def _series_init(prog):
    return jnp.zeros((prog.max_rounds, 2 + len(prog.probe_names)),
                     jnp.float32)


@device_scope("superstep.telemetry")
def _series_write(prog, series, r, state):
    halted = jnp.asarray(prog.halt(state)).astype(jnp.float32).reshape(())
    probes = tuple(prog.probe(state)) if prog.probe is not None else ()
    if len(probes) != len(prog.probe_names):
        raise ValueError(
            f"{prog.key}: probe() returned {len(probes)} values for "
            f"probe_names {prog.probe_names!r}")
    row = jnp.stack(
        [jnp.float32(1.0), halted]
        + [jnp.asarray(p).astype(jnp.float32).reshape(()) for p in probes])
    return series.at[r].set(row)


# --------------------------------------------------------------------------
# Guard machinery.  A guard run folds THREE signals into one per-round
# uniform ``ok`` scalar: the program's invariant verdict (or the default
# NaN/Inf screen), the transport-stamp violations drained from the fault
# taps, and the previous round's ok (sticky — once bad, stays bad so the
# loop exits and the caller can roll back).
# --------------------------------------------------------------------------


def finite_state(state):
    """Default guard: every float leaf of the state is finite."""
    ok = jnp.bool_(True)
    for leaf in jax.tree_util.tree_leaves(state):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            ok = ok & jnp.isfinite(leaf).all()
    return ok


@device_scope("superstep.guard")
def _round_ok(prog, g, prev, state):
    """Uniform per-round verdict: invariant guard AND transport stamps."""
    gfn = prog.guard if prog.guard is not None \
        else (lambda g_, p_, s_: finite_state(s_))
    local = jnp.asarray(gfn(g, prev, state), bool)
    ok = psum_scalar(local.astype(jnp.int32)) == jax.lax.axis_size(AXIS)
    viol = faults.stamp_violation()
    if viol is not None:
        ok = ok & jnp.logical_not(viol)
    return ok


def run_program_async(prog: AsyncSuperstepProgram, g: dict, *inputs,
                      static_iters: int = 0, guard: bool = False,
                      telemetry: bool = False):
    """The double-buffered driver: same ``(outputs, rounds)`` contract
    as :func:`run_program`, same while/scan split, but each round is
    ``local`` (overlap window) then ``fold`` (finish + restart the
    exchange), with the in-flight handle carried across iterations.

    Fault-round addressing: the exchange issued by ``init`` is round 0;
    the one started in body iteration ``r`` is round ``r + 1`` (the
    (k+1)-th exchange started is round k+1).  With ``guard=True`` the
    return gains ``ok``; with ``telemetry=True`` it gains the series
    buffer (always LAST): ``(outputs, rounds[, ok][, series])``.
    """
    if telemetry and static_iters:
        raise ValueError("telemetry requires the while-loop driver "
                         "(static_iters=0)")
    obs_tel.phase("init")
    faults.set_round(jnp.int32(0))
    with device_scope("superstep.init"):
        g = prog.prepare(g)
        state0, handle0 = prog.init(g, *inputs)

    if static_iters:
        def sbody(carry, _):
            state, handle, r = carry
            faults.set_round(r + 1)
            with device_scope("superstep.step"):
                state, handle = prog.fold(g, prog.local(g, state), handle)
                return (state, handle, r + 1), None

        obs_tel.phase("round")
        with device_scope("superstep.loop"):
            (state, _, rounds), _ = jax.lax.scan(
                sbody, (state0, handle0, jnp.int32(0)), None,
                length=static_iters)
        faults.set_round(jnp.int32(-1))   # outputs are not addressable
        obs_tel.phase("outputs")
        with device_scope("superstep.outputs"):
            return prog.outputs(g, state), rounds

    ok0 = _round_ok(prog, g, state0, state0) if guard else ()
    series0 = _series_init(prog) if telemetry else ()

    @device_scope("superstep.halt")
    def cond(carry):
        state, _, r, ok, _series = carry
        live = jnp.logical_not(prog.halt(state)) & (r < prog.max_rounds)
        return (ok & live) if guard else live

    def body(carry):
        state, handle, r, ok, series = carry
        faults.set_round(r + 1)
        prev = state
        with device_scope("superstep.step"):
            state, handle = prog.fold(g, prog.local(g, state), handle)
            r_next = r + 1
        if guard:
            ok = ok & _round_ok(prog, g, prev, state)
        if telemetry:
            series = _series_write(prog, series, r, state)
        return state, handle, r_next, ok, series

    obs_tel.phase("round")
    with device_scope("superstep.loop"):
        state, _, rounds, ok, series = jax.lax.while_loop(
            cond, body, (state0, handle0, jnp.int32(0), ok0, series0))
    faults.set_round(jnp.int32(-1))
    obs_tel.phase("outputs")
    with device_scope("superstep.outputs"):
        res = (prog.outputs(g, state), rounds)
    if guard:
        res += (ok,)
    if telemetry:
        res += (series,)
    return res


@dataclass(frozen=True)
class PhasedProgram:
    """A multi-phase algorithm: a tuple of :class:`SuperstepProgram`s run
    back to back, each phase's ``outputs`` threaded into the next phase's
    ``init`` (after the per-query ``inputs`` of phase 0).

    Brandes betweenness is the motivating case: a forward
    shortest-path-counting BFS, then a dependency-accumulation backward
    sweep seeded with the forward (dist, sigma) fields.  The driver is
    still :func:`run_program` — it dispatches to :func:`run_phases` — so
    every engine layer (compile cache, batching, dry-run static_iters)
    works on phased programs with no extra plumbing.

    ``output_names`` / ``output_is_vertex`` describe the LAST phase's
    outputs, which are the program's outputs.
    """

    name: str
    variant: str
    inputs: tuple[str, ...]
    phases: tuple[SuperstepProgram, ...]
    output_names: tuple[str, ...]
    output_is_vertex: tuple[bool, ...]

    @property
    def key(self) -> str:
        return f"{self.name}/{self.variant}"

    @property
    def probe_names(self) -> tuple[str, ...]:
        """Telemetry probes of a phased program: the phases share ONE
        series buffer layout, so every phase must declare the same
        probe names (phase 0's are canonical)."""
        names = self.phases[0].probe_names
        for ph in self.phases[1:]:
            if ph.probe_names != names:
                raise ValueError(
                    f"{self.key}: phases declare different probe_names "
                    f"({names!r} vs {ph.probe_names!r}); telemetry "
                    "needs one row layout")
        return names


def run_phases(prog: PhasedProgram, g: dict, *inputs,
               static_iters: int = 0, guard: bool = False,
               telemetry: bool = False):
    """Chain the phases of a :class:`PhasedProgram`: phase ``i+1`` is
    initialized with phase ``i``'s outputs.  Returns the last phase's
    outputs and the TOTAL round count (each phase runs ``static_iters``
    supersteps on the scan path, so the total is ``len(phases) *
    static_iters`` there).  Fault rounds address each phase's own
    counter (a round-2 event fires in EVERY phase's round 2).  Under
    ``guard=True`` the per-phase ok scalars AND together.  Under
    ``telemetry=True`` the per-phase series buffers concatenate (valid
    rows stay marked by the ``done`` column; the host trims)."""
    if telemetry:
        prog.probe_names        # raises if phases disagree on layout
    chained = inputs
    total = jnp.int32(0)
    ok = jnp.bool_(True)
    series_parts = []
    for phase in prog.phases:
        res = run_program(phase, g, *chained, static_iters=static_iters,
                          guard=guard, telemetry=telemetry)
        if telemetry:
            series_parts.append(res[-1])
            res = res[:-1]
        if guard:
            chained, rounds, phase_ok = res
            ok = ok & phase_ok
        else:
            chained, rounds = res
        total = total + rounds
    out = (chained, total) + ((ok,) if guard else ())
    if telemetry:
        out += (jnp.concatenate(series_parts, axis=0),)
    return out


def run_program(prog, g: dict, *inputs, static_iters: int = 0,
                guard: bool = False, telemetry: bool = False):
    """The ONE shared superstep driver (call inside shard_map).

    Returns ``(outputs_tuple, rounds)`` where ``rounds`` is the number of
    supersteps executed (== ``static_iters`` on the scan path).  A
    :class:`PhasedProgram` dispatches to :func:`run_phases`.

    ``guard=True`` compiles the per-round invariant check in: the while
    cond gains a sticky uniform ``ok`` scalar (invariant guard AND
    fault-transport stamps), the loop exits on the FIRST violated round,
    and the return becomes ``(outputs_tuple, rounds, ok)``.  Not
    supported on the ``static_iters`` scan path (the dry-run costs a
    clean loop).

    ``telemetry=True`` compiles the per-round series write in (see the
    series block above) and appends the ``(max_rounds, 2 + K)`` buffer
    as the LAST return element.  Composes with ``guard``; like it,
    incompatible with ``static_iters``.  The off path carries ``()`` in
    the series slot — zero extra leaves, bit-identical outputs.
    """
    if guard and static_iters:
        raise ValueError("guard=True is incompatible with static_iters")
    if telemetry and static_iters:
        raise ValueError("telemetry requires the while-loop driver "
                         "(static_iters=0)")
    if isinstance(prog, PhasedProgram):
        return run_phases(prog, g, *inputs, static_iters=static_iters,
                          guard=guard, telemetry=telemetry)
    if isinstance(prog, AsyncSuperstepProgram):
        return run_program_async(prog, g, *inputs,
                                 static_iters=static_iters, guard=guard,
                                 telemetry=telemetry)
    obs_tel.phase("init")
    faults.set_round(jnp.int32(0))
    with device_scope("superstep.init"):
        g = prog.prepare(g)
        state0 = prog.init(g, *inputs)

    if static_iters:
        def sbody(carry, _):
            state, r = carry
            faults.set_round(r)
            with device_scope("superstep.step"):
                return (prog.step(g, state), r + 1), None

        obs_tel.phase("round")
        with device_scope("superstep.loop"):
            (state, rounds), _ = jax.lax.scan(
                sbody, (state0, jnp.int32(0)), None, length=static_iters)
        faults.set_round(jnp.int32(-1))   # outputs are not addressable
        obs_tel.phase("outputs")
        with device_scope("superstep.outputs"):
            return prog.outputs(state), rounds

    ok0 = _round_ok(prog, g, state0, state0) if guard else ()
    series0 = _series_init(prog) if telemetry else ()

    @device_scope("superstep.halt")
    def cond(carry):
        state, r, ok, _series = carry
        live = jnp.logical_not(prog.halt(state)) & (r < prog.max_rounds)
        return (ok & live) if guard else live

    def body(carry):
        state, r, ok, series = carry
        faults.set_round(r)
        with device_scope("superstep.step"):
            new = prog.step(g, state)
            r_next = r + 1
        if guard:
            ok = ok & _round_ok(prog, g, state, new)
        if telemetry:
            series = _series_write(prog, series, r, new)
        return new, r_next, ok, series

    obs_tel.phase("round")
    with device_scope("superstep.loop"):
        state, rounds, ok, series = jax.lax.while_loop(
            cond, body, (state0, jnp.int32(0), ok0, series0))
    faults.set_round(jnp.int32(-1))
    obs_tel.phase("outputs")
    with device_scope("superstep.outputs"):
        res = (prog.outputs(state), rounds)
    if guard:
        res += (ok,)
    if telemetry:
        res += (series,)
    return res


def run_program_batched(prog, g: dict, *batched_inputs,
                        static_iters: int = 0):
    """Multi-source driver: vmap :func:`run_program` over (B,)-batched
    query inputs (e.g. BFS/SSSP roots), amortizing one graph residency
    across B traversals — the serve-many-queries path.

    Vertex outputs gain a leading (B,) axis; ``rounds`` becomes (B,).
    Works for :class:`PhasedProgram` too (batched betweenness: B forward
    sweeps then B backward sweeps, vmapped as one phased traversal).
    The ELL gathers keep the lane axis leading under vmap (see
    ``core/localops.py``), so a lane costs its own temporaries only.
    """
    if not isinstance(prog, PhasedProgram):
        # hoist the loop-invariant prepare out of the vmap so per-query
        # traversals share one derived-edge-data computation
        with device_scope("superstep.init"):
            g = prog.prepare(g)
        prog = dataclasses.replace(prog, prepare=lambda garr: garr)

    def one(*ins):
        outs, rounds = run_program(prog, g, *ins,
                                   static_iters=static_iters)
        return (*outs, rounds)

    res = jax.vmap(one)(*batched_inputs)
    return res[:-1], res[-1]


# --------------------------------------------------------------------------
# Chunked execution: the checkpointing substrate.
#
# ``core/recovery.py`` drives a program as a sequence of guarded CHUNKS of
# at most k rounds, snapshotting the carry to host between chunks.  The
# carry is ``(state, handle, rounds, ok)`` — handle is ``()`` for BSP
# programs, the in-flight exchange for async ones (it is plain array
# data, so it checkpoints and restores like any state leaf).  Chunking
# never changes the traced per-round computation, so a chunked run is
# bit-identical to the guarded un-chunked driver, which is bit-identical
# to the plain driver on fault-free rounds.
# --------------------------------------------------------------------------


def init_carry(prog, g: dict, *inputs, telemetry: bool = False):
    """Build the initial checkpointable carry ``(state, handle, rounds,
    ok)`` — prepare + init + the round-0 verdict (init-time exchanges
    are fault-addressable as round 0, so a tainted init reports
    ``ok=False`` and the caller re-inits clean rather than checkpointing
    poison).  ``telemetry=True`` appends the series buffer as carry[4]
    — it checkpoints, rolls back, and restores like any state leaf, so
    a recovered run's series has no rows from discarded chunks."""
    obs_tel.phase("init")
    faults.set_round(jnp.int32(0))
    with device_scope("superstep.init"):
        g = prog.prepare(g)
        if isinstance(prog, AsyncSuperstepProgram):
            state0, handle0 = prog.init(g, *inputs)
        else:
            state0 = prog.init(g, *inputs)
            handle0 = ()
    ok0 = _round_ok(prog, g, state0, state0)
    base = (state0, handle0, jnp.int32(0), ok0)
    return base + (_series_init(prog),) if telemetry else base


def run_chunk(prog, g: dict, carry, chunk: int):
    """Advance ``carry`` by up to ``chunk`` guarded rounds.

    Exits early on halt, ``max_rounds``, or the first violated round
    (sticky ``ok``).  Returns ``(carry, halted)``; the caller inspects
    ``carry[3]`` (ok) to decide checkpoint vs rollback and ``halted`` /
    ``carry[2]`` (rounds) to decide whether to keep chunking.  A
    5-element carry (from ``init_carry(telemetry=True)``) carries the
    telemetry series and writes its row each round.
    """
    with device_scope("superstep.init"):
        g = prog.prepare(g)
    is_async = isinstance(prog, AsyncSuperstepProgram)
    telemetry = len(carry) == 5

    @device_scope("superstep.halt")
    def cond(c):
        (state, _, r, ok, *_), i = c
        return ok & jnp.logical_not(prog.halt(state)) \
            & (i < chunk) & (r < prog.max_rounds)

    def body(c):
        (state, handle, r, ok, *rest), i = c
        faults.set_round(r + 1 if is_async else r)
        prev = state
        with device_scope("superstep.step"):
            if is_async:
                state, handle = prog.fold(g, prog.local(g, state), handle)
            else:
                state = prog.step(g, state)
            r_next, i_next = r + 1, i + 1
        ok = ok & _round_ok(prog, g, prev, state)
        new = (state, handle, r_next, ok)
        if telemetry:
            new += (_series_write(prog, rest[0], r, state),)
        return new, i_next

    obs_tel.phase("round")
    with device_scope("superstep.loop"):
        carry, _ = jax.lax.while_loop(cond, body, (carry, jnp.int32(0)))
    faults.set_round(jnp.int32(-1))
    return carry, jnp.asarray(prog.halt(carry[0]), bool)


def carry_outputs(prog, g: dict, carry):
    """Finalize a halted carry into the program's outputs tuple."""
    faults.set_round(jnp.int32(-1))
    state = carry[0]
    with device_scope("superstep.outputs"):
        g = prog.prepare(g)
        if isinstance(prog, AsyncSuperstepProgram):
            return prog.outputs(g, state)
        return prog.outputs(state)
