"""Checkpointed, fault-recovering execution of superstep programs.

``core/superstep.py`` supplies the chunked substrate (``init_carry`` /
``run_chunk`` / ``carry_outputs``); this module owns the HOST loop that
turns it into fault tolerance:

  * every ``checkpoint_every`` rounds the full loop carry — vertex
    state, in-flight async handle, round counter, guard verdict — is
    snapshotted to host memory (``Checkpoint``);
  * each chunk runs the GUARDED driver: the program's per-round
    invariant check plus the transport-stamp detector (``core/faults``)
    stop the loop on the first violated round;
  * on detection the runner restores the last checkpoint and replays
    the chunk with a CLEAN-compiled executable (no fault taps) — the
    transient-fault model: the injected fault belongs to one execution
    of those rounds, not to the rounds themselves.  Later chunks resume
    the fault-compiled executable, so later-round events still fire
    (and are recovered in turn).  A violation that SURVIVES a clean
    replay is a real algorithm/guard bug and raises
    :class:`RecoveryError` instead of looping;
  * ``run(..., resume_from=checkpoint)`` restarts from any snapshot.

Chunking never changes the traced per-round computation, and the
host round-trip (``device_get`` / ``device_put``) is bit-exact, so a
checkpointed, resumed, or recovered run produces BIT-IDENTICAL outputs
to an uninterrupted one (pagerank included — same arithmetic, same
order), which is what ``tests/test_chaos.py`` pins for every registered
program.

Everything crosses the shard_map boundary through one universal
wrapping rule: each per-shard leaf gains a leading axis of size 1
(globally: the ``parts`` axis), with a single ``P("parts")`` pytree
prefix as its spec — scalars, handles, vertex fields and round
counters all ride the same path, so the carry needs no per-leaf spec
bookkeeping.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import faults as faults_mod
from repro.core import registry
from repro.core.api import _graph_specs
from repro.core.superstep import PhasedProgram, carry_outputs, init_carry, \
    run_chunk
from repro.obs import telemetry as obs_telemetry
from repro.obs.spans import NULL_RECORDER

P = jax.sharding.PartitionSpec


class RecoveryError(RuntimeError):
    """A guard violation that checkpoint rollback cannot clear."""


def _wrap(tree):
    """Per-shard -> global: every leaf gains a leading parts axis."""
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], tree)


def _unwrap(tree):
    """Global -> per-shard: strip the leading parts axis."""
    return jax.tree_util.tree_map(lambda x: x[0], tree)


@dataclass(frozen=True)
class Checkpoint:
    """A host-resident snapshot of one phase's loop carry.

    ``carry`` is the wrapped global form (numpy): restoring it is one
    ``device_put`` per leaf against the runner's parts sharding, which
    round-trips bits exactly.
    """

    phase: int
    rounds: int
    carry: Any


@dataclass
class RunReport:
    """What a checkpointed run did, beyond its outputs.

    ``outputs`` matches the engine convention: vertex fields arrive as
    (P, n_local) numpy arrays (``engine.gather_vertex_field`` applies),
    scalars as numpy scalars.  ``detections`` lists the round counter
    at each guard/transport detection (the first tainted round + 1);
    ``recoveries`` counts rollback-replays that cleared one.
    """

    outputs: tuple
    rounds: int
    recoveries: int = 0
    detections: tuple = ()
    checkpoints: int = 0
    history: tuple = ()
    telemetry: dict | None = None


class CheckpointRunner:
    """Run one registered program with superstep checkpointing, fault
    injection, and rollback recovery.

        runner = CheckpointRunner(engine, "bfs", "fast",
                                  checkpoint_every=2,
                                  faults="corrupt@r3p1:sum seed=7")
        report = runner.run(engine.device_graph(), jnp.int32(root))

    ``faults=None`` gives plain checkpointed execution (the
    checkpoint/resume bit-identity path); a
    :class:`~repro.core.faults.FaultSchedule` (or its string spec)
    compiles deterministic fault injection into the exchange taps of
    the PRIMARY executables — the recovery replays always run clean
    ones.  ``keep_history=True`` retains every checkpoint in the
    report (tests resume from a mid-run snapshot).
    """

    def __init__(self, engine, algo: str, variant: str | None = None, *,
                 checkpoint_every: int = 2, faults=None,
                 max_recoveries: int = 16, keep_history: bool = False,
                 telemetry: bool = False, obs=None, **params):
        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")
        self.engine = engine
        self.spec = registry.get_spec(algo, variant)
        self.schedule = faults_mod.as_schedule(faults)
        self.checkpoint_every = int(checkpoint_every)
        self.max_recoveries = int(max_recoveries)
        self.keep_history = bool(keep_history)
        # telemetry rides the carry as carry[4] (see superstep series
        # block): it checkpoints and rolls back with the state, so a
        # recovered run's series has no rows from discarded chunks.
        # ``obs`` is a SpanRecorder: chunk spans plus checkpoint /
        # fault_detection / rollback instant events on the recovery
        # track (NULL_RECORDER = off, the default).
        self.telemetry = bool(telemetry)
        self.wire = obs_telemetry.WireRecord() if telemetry else None
        self.obs = obs if obs is not None else NULL_RECORDER
        prog = self.spec.build(engine.g, **params)
        self.program = prog
        self.phases = prog.phases if isinstance(prog, PhasedProgram) \
            else (prog,)
        self._sh = jax.sharding.NamedSharding(engine.mesh, P("parts"))
        self._gspecs = _graph_specs(engine.g, engine.layout)
        self._pieces: dict = {}

    # -- compiled pieces ----------------------------------------------------

    def _ctx(self, faulty: bool):
        if faulty and self.schedule is not None:
            return faults_mod.active(self.schedule, detect=True)
        return contextlib.nullcontext()

    def _jit(self, fn, in_specs):
        return jax.jit(jax.shard_map(
            fn, mesh=self.engine.mesh, in_specs=in_specs,
            out_specs=P("parts"), check_vma=False))

    def _init_piece(self, pi: int, faulty: bool):
        key = ("init", pi, faulty)
        if key in self._pieces:
            return self._pieces[key]
        prog = self.phases[pi]
        if pi == 0:
            kinds = self.spec.input_kinds

            def fn(garr, *inputs):
                garr = {k: v[0] for k, v in garr.items()}
                ins = tuple(x[0] if kind != "scalar" else x
                            for x, kind in zip(inputs, kinds))
                with self._ctx(faulty):
                    return _wrap(init_carry(prog, garr, *ins,
                                            telemetry=self.telemetry))

            in_specs = (self._gspecs,) + tuple(
                P() if kind == "scalar" else P("parts", None)
                for kind in kinds)
        else:
            # later phases are initialized from the previous phase's
            # wrapped outputs — unwrap uniformly
            def fn(garr, *chained):
                garr = {k: v[0] for k, v in garr.items()}
                ins = tuple(x[0] for x in chained)
                with self._ctx(faulty):
                    return _wrap(init_carry(prog, garr, *ins,
                                            telemetry=self.telemetry))

            n_prev = len(self.phases[pi - 1].output_names)
            in_specs = (self._gspecs,) + (P("parts"),) * n_prev
        piece = self._jit(fn, in_specs)
        self._pieces[key] = piece
        return piece

    def _chunk_piece(self, pi: int, faulty: bool):
        key = ("chunk", pi, faulty)
        if key in self._pieces:
            return self._pieces[key]
        prog = self.phases[pi]
        k = self.checkpoint_every

        def fn(garr, carry):
            garr = {k2: v[0] for k2, v in garr.items()}
            # arm the wire record during the chunk trace: the chunk body
            # IS the per-round loop, so its taps are the per-round bytes
            tcm = obs_telemetry.recording(self.wire) if self.telemetry \
                else contextlib.nullcontext()
            with self._ctx(faulty), tcm:
                carry2, halted = run_chunk(prog, garr, _unwrap(carry), k)
            return _wrap((carry2, halted))

        piece = self._jit(fn, (self._gspecs, P("parts")))
        self._pieces[key] = piece
        return piece

    def _out_piece(self, pi: int):
        key = ("out", pi)
        if key in self._pieces:
            return self._pieces[key]
        prog = self.phases[pi]

        def fn(garr, carry):
            garr = {k: v[0] for k, v in garr.items()}
            return _wrap(tuple(carry_outputs(prog, garr, _unwrap(carry))))

        piece = self._jit(fn, (self._gspecs, P("parts")))
        self._pieces[key] = piece
        return piece

    # -- host-side carry plumbing -------------------------------------------

    @staticmethod
    def _ok(carry) -> bool:
        return bool(np.asarray(carry[3])[0])

    @staticmethod
    def _rounds(carry) -> int:
        return int(np.asarray(carry[2])[0])

    def _snapshot(self, pi: int, carry) -> Checkpoint:
        return Checkpoint(phase=pi, rounds=self._rounds(carry),
                          carry=jax.device_get(carry))

    def _restore(self, host_carry):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._sh), host_carry)

    # -- the recovery loop --------------------------------------------------

    def _run_phase(self, pi: int, garr, inputs, stats: dict,
                   resume: Checkpoint | None):
        if resume is not None:
            carry = self._restore(resume.carry)
        else:
            carry = self._init_piece(pi, True)(garr, *inputs)
            if not self._ok(carry):
                stats["detections"].append(self._rounds(carry))
                self.obs.event("fault_detection", "recovery", phase=pi,
                               round=self._rounds(carry))
                self._bump(stats)
                self.obs.event("rollback", "recovery", phase=pi,
                               to_rounds=0)
                carry = self._init_piece(pi, False)(garr, *inputs)
                if not self._ok(carry):
                    raise RecoveryError(
                        f"{self.spec.key} phase {pi}: clean re-init "
                        f"still violates guards")
        ck = self._snapshot(pi, carry)
        stats["checkpoints"] += 1
        self.obs.event("checkpoint", "recovery", phase=pi,
                       rounds=ck.rounds)
        if self.keep_history:
            stats["history"].append(ck)
        while True:
            r0 = self._rounds(carry)
            with self.obs.span("chunk", "recovery", phase=pi,
                               from_round=r0) as chunk_span:
                nxt, halted = self._chunk_piece(pi, True)(garr, carry)
                if not self._ok(nxt):
                    stats["detections"].append(self._rounds(nxt))
                    self.obs.event("fault_detection", "recovery",
                                   phase=pi, round=self._rounds(nxt))
                    self._bump(stats)
                    self.obs.event("rollback", "recovery", phase=pi,
                                   to_rounds=ck.rounds)
                    carry = self._restore(ck.carry)
                    nxt, halted = self._chunk_piece(pi, False)(garr,
                                                               carry)
                    if not self._ok(nxt):
                        raise RecoveryError(
                            f"{self.spec.key} phase {pi}: guard "
                            f"violation at round {self._rounds(nxt)} "
                            f"persists on clean replay from the "
                            f"round-{ck.rounds} checkpoint")
                carry = nxt
                chunk_span.args["to_round"] = self._rounds(carry)
            ck = self._snapshot(pi, carry)
            stats["checkpoints"] += 1
            self.obs.event("checkpoint", "recovery", phase=pi,
                           rounds=ck.rounds)
            if self.keep_history:
                stats["history"].append(ck)
            if bool(np.asarray(halted)[0]) or self._rounds(carry) == r0:
                return carry

    def _bump(self, stats: dict):
        stats["recoveries"] += 1
        if stats["recoveries"] > self.max_recoveries:
            raise RecoveryError(
                f"{self.spec.key}: exceeded max_recoveries="
                f"{self.max_recoveries}")

    def run(self, garr, *inputs, resume_from: Checkpoint | None = None):
        """Execute (or resume) the program; returns a :class:`RunReport`.

        ``garr`` is ``engine.device_graph()``; ``inputs`` follow the
        spec's input kinds exactly like a :class:`CompiledProgram`
        call.  ``resume_from`` restarts from a snapshot: phases before
        it are already folded into its carry, later phases run
        normally.
        """
        stats = {"recoveries": 0, "detections": [], "checkpoints": 0,
                 "history": []}
        start = resume_from.phase if resume_from is not None else 0
        total = 0
        chained = inputs
        carry = None
        series_rows = []
        for pi in range(start, len(self.phases)):
            resume = resume_from if (resume_from is not None
                                     and pi == start) else None
            carry = self._run_phase(pi, garr, chained, stats, resume)
            total += self._rounds(carry)
            if self.telemetry:
                # wrapped global series: (P, max_rounds, 2 + K),
                # replicated — any part's copy is the run's series
                series_rows.append(np.asarray(carry[4])[0])
            if pi + 1 < len(self.phases):
                chained = self._out_piece(pi)(garr, carry)
        outs = self._out_piece(len(self.phases) - 1)(garr, carry)
        host = tuple(
            np.asarray(o) if is_v else np.asarray(o)[0]
            for o, is_v in zip(outs, self.program.output_is_vertex))
        telemetry = None
        if self.telemetry:
            ps = obs_telemetry.PhaseSeries.from_array(
                np.concatenate(series_rows, axis=0),
                self.program.probe_names)
            telemetry = obs_telemetry.RunTelemetry(
                series=ps, wire=self.wire.snapshot()).summary()
        return RunReport(
            outputs=host, rounds=total,
            recoveries=stats["recoveries"],
            detections=tuple(stats["detections"]),
            checkpoints=stats["checkpoints"],
            history=tuple(stats["history"]),
            telemetry=telemetry)
