"""Public graph-engine API: registry-driven superstep programs compiled
as jitted shard_map executables over a 1-D mesh.

``GraphEngine`` binds a partitioned graph to a mesh.  The single entry
point is :meth:`GraphEngine.program`:

    prog = engine.program("bfs", "fast", max_levels=32)
    parents, levels = prog(engine.device_graph(), jnp.int32(root))

``program()`` resolves the (algo, variant) pair through
``core/registry.py``, wraps the program's ``init/step/halt/outputs``
with the ONE shared superstep driver (``core/superstep.py``), and caches
the resulting compiled callable keyed on algorithm + params + graph
shapes + mesh — repeated calls return the SAME object, so nothing
re-traces.  ``batch=B`` builds the multi-source variant (roots shaped
(B,), vmapped inside the shard program).  The legacy ``bfs()/pagerank()/
sssp()/cc()`` methods are thin delegating wrappers.

The same callables lower against abstract inputs for the multi-pod
dry-run (core/dryrun.py) via :meth:`CompiledProgram.lower` /
:meth:`CompiledProgram.aot`.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import localops, registry
from repro.core import faults as faults_mod
from repro.core.graph import GraphShards
from repro.core.superstep import run_program, run_program_batched
from repro.obs import scopes as obs_scopes
from repro.obs import telemetry as obs_telemetry
from repro.obs.spans import annotate

P = jax.sharding.PartitionSpec

# jnp dtype of each registry input kind; "scalar" inputs are replicated
# per-query values, vertex kinds are (P, n_local) sharded fields (the
# warm seeds of the incremental variants)
_KIND_DTYPE = {"scalar": jnp.int32,
               "vertex_i32": jnp.int32,
               "vertex_f32": jnp.float32}


def _graph_specs(g: GraphShards, layout: str):
    return {k: P("parts", None) for k in g.abstract_arrays(layout)}


class CompiledProgram:
    """A cached, callable, AOT-lowerable superstep program.

    ``__call__`` runs the jitted executable (jit's trace cache makes
    repeated calls free); ``lower()``/``aot()`` expose the AOT path the
    dry-run and roofline tooling use.  Instances are interned by
    :meth:`GraphEngine.program`, so object identity doubles as the
    compile-cache hit test.
    """

    def __init__(self, spec, program, fn, abstract_args,
                 guarded=False, faults=None, telemetry=False, wire=None):
        self.spec = spec                  # registry ProgramSpec
        self.program = program            # SuperstepProgram instance
        self.fn = fn                      # jitted shard_map callable
        self.abstract_args = abstract_args
        self.guarded = guarded            # trailing ok output appended
        self.faults = faults              # FaultSchedule or None
        self.telemetry = telemetry        # trailing series output appended
        self.wire = wire                  # obs WireRecord (telemetry builds)
        self.last_wall_s = 0.0            # telemetry-mode host wall-time
        self._aot = None

    def __call__(self, garr, *inputs):
        if not self.telemetry:
            with annotate("engine.call"):
                return self.fn(garr, *inputs)
        # telemetry builds are MEASUREMENT mode: block on the result so
        # the recorded wall-time covers the device work, not just the
        # dispatch (documented perturbation — don't time the dispatch
        # overlap through a telemetry build)
        t0 = time.perf_counter()
        with annotate("engine.call"):
            out = self.fn(garr, *inputs)
        jax.block_until_ready(out)
        self.last_wall_s = time.perf_counter() - t0
        return out

    def run_telemetry(self, series) -> "obs_telemetry.RunTelemetry":
        """Parse the trailing series output of a telemetry run into a
        ``RunTelemetry`` carrying this build's trace-time wire snapshot
        and the last ``__call__``'s wall-time."""
        if not self.telemetry:
            raise ValueError(f"{self.program.key} was not built with "
                             "telemetry=True")
        ps = obs_telemetry.PhaseSeries.from_array(
            np.asarray(series), self.program.probe_names)
        return obs_telemetry.RunTelemetry(
            series=ps, wire=self.wire.snapshot(), wall_s=self.last_wall_s)

    def lower(self, *args):
        """AOT-lower; defaults to the engine's abstract arg shapes.  The
        executable its ``compile()`` returns is kept by ``repro.obs``
        (``obs.scopes.compiled_scopes``), so a profiler trace of it can
        be read by device scope."""
        with annotate("engine.lower"):
            return _Lowered(self.fn.lower(*(args if args else
                                            self.abstract_args)))

    def aot(self):
        """Lowered + compiled executable against abstract args (cached)."""
        if self._aot is None:
            self._aot = self.lower().compile()
        return self._aot

    def trace_cache_size(self) -> int:
        """Number of traces jit holds for this callable (1 after warmup)."""
        return self.fn._cache_size()

    def __repr__(self):
        return (f"CompiledProgram({self.program.key}, "
                f"inputs={self.spec.inputs})")


class _Lowered:
    """A ``jax.stages.Lowered`` whose ``compile()`` hands the executable
    to :func:`repro.obs.scopes.keep`; every other attribute is the
    wrapped object's."""

    def __init__(self, lowered):
        self._lowered = lowered

    def compile(self, *args, **kwargs):
        compiled = self._lowered.compile(*args, **kwargs)
        obs_scopes.keep(compiled)
        return compiled

    def __getattr__(self, name):
        return getattr(self._lowered, name)


@dataclass
class GraphEngine:
    g: GraphShards
    mesh: jax.sharding.Mesh
    # "ell" ships the blocked-ELL arrays so localops takes the tuned
    # gather path; "coo" withholds them - every program then traces the
    # reference scatter idiom (the escape hatch behind --layout coo)
    layout: str = "ell"
    _cache: dict = field(default_factory=dict, repr=False, compare=False)

    # -- the program API ----------------------------------------------------
    def program(self, algo: str, variant: str | None = None, *,
                static_iters: int = 0, batch: int | None = None,
                exec_mode: str | None = None, guard: bool = False,
                faults=None, telemetry: bool = False,
                **params) -> CompiledProgram:
        """Resolve, build, wrap and cache an algorithm program.

        ``static_iters > 0`` replaces the early-exit while loop with a
        fixed-trip scan (dry-run/roofline path).  ``batch=B`` compiles
        the multi-source variant: every ("root",)-style input becomes a
        (B,) array and vertex outputs gain a leading (P, B, ...) batch
        axis.  ``exec_mode`` selects the superstep driver by mode
        instead of variant name: with a bare algo it re-resolves to the
        algo's variant of that mode (``program("bfs",
        exec_mode="async")`` is ``program("bfs", "async")``); with an
        explicit variant it is a consistency ASSERTION and a mismatch
        raises rather than silently running the other driver.

        ``guard=True`` compiles the GUARDED driver: the program's
        per-round invariant check (``core/faults`` docs) plus the
        transport-stamp detector run every round, the loop stops on the
        first violation, and ONE extra replicated int32 output (1 = run
        clean, 0 = violation detected) is appended after ``rounds``.
        ``faults=`` takes a :class:`repro.core.faults.FaultSchedule`
        (or its string spec) and compiles deterministic fault injection
        into the exchange taps — detection fires only when ``guard``
        is also set.  Neither composes with ``batch``/``static_iters``
        (checkpointed recovery lives in ``core/recovery.py``).

        ``telemetry=True`` compiles the per-round telemetry series in
        (``core/superstep.py`` series block): ONE extra replicated
        ``(max_rounds, 2 + K)`` f32 output is appended LAST, trace-time
        wire bytes are captured on :attr:`CompiledProgram.wire`, and
        ``__call__`` blocks on the result to measure host wall-time —
        parse it all with :meth:`CompiledProgram.run_telemetry`.
        Composes with ``guard``; like it, incompatible with ``batch``
        and ``static_iters``.  ``telemetry=False`` builds are
        bit-identical to pre-telemetry builds (asserted in tests).

        The cache key covers algo, variant, params, loop mode, exec
        mode, guard/fault schedule, telemetry, graph shapes and mesh,
        so repeated calls return the same object and never re-trace.
        """
        bare = variant is None and "/" not in algo
        spec = registry.get_spec(algo, variant)
        if exec_mode is not None and spec.exec_mode != exec_mode:
            if exec_mode not in registry.EXEC_MODES:
                raise ValueError(
                    f"exec_mode {exec_mode!r} not in {registry.EXEC_MODES}")
            if not bare:
                raise ValueError(
                    f"{spec.key} is a {spec.exec_mode} program; "
                    f"exec_mode={exec_mode!r} contradicts the explicit "
                    f"variant — drop one (mode-variants: "
                    f"{registry.mode_variant(spec.algo, exec_mode)!r})")
            alt = registry.mode_variant(spec.algo, exec_mode)
            if alt is None:
                raise ValueError(
                    f"{spec.algo} has no {exec_mode} variant; "
                    f"async-capable pairs: "
                    f"{['/'.join(p) for p in registry.async_pairs()]}")
            spec = registry.get_spec(spec.algo, alt)
        if batch is not None and not spec.inputs:
            raise ValueError(
                f"{spec.key} takes no per-query inputs; batch="
                f"{batch} has nothing to vmap over")
        if batch is not None and any(k != "scalar" for k in spec.input_kinds):
            raise ValueError(
                f"{spec.key} takes whole vertex-field inputs "
                f"{spec.inputs}; only scalar per-query inputs batch")
        schedule = faults_mod.as_schedule(faults)
        if guard and static_iters:
            raise ValueError(
                "guard=True is incompatible with static_iters: the "
                "guarded loop must stop on the detected round")
        if (guard or schedule is not None) and batch is not None:
            raise ValueError(
                "guard/faults do not compose with batch: fault rounds "
                "and guard verdicts are per-run, not per-lane")
        if telemetry and static_iters:
            raise ValueError(
                "telemetry requires the while-loop driver; the "
                "static_iters dry-run has no data-dependent rounds to "
                "record")
        if telemetry and batch is not None:
            raise ValueError(
                "telemetry does not compose with batch: the series is "
                "per-run, not per-lane")
        # normalize params into full (defaults + overrides) form so an
        # explicitly spelled default hits the same cache entry; batched
        # builds additionally merge the spec's vmap-friendly overrides
        # (e.g. bfs/fast pins direction="pull": a per-lane cond would
        # run both branches under vmap).  Explicit caller params win.
        batch_over = spec.batch_defaults if batch is not None else {}
        params = {**spec.defaults, **batch_over, **params}
        g = self.g
        # the layout and localops mode steer TRACE-time dispatch in
        # core/localops.py, so both belong in the compile-cache key
        # layout_signature covers the blocked-ELL bucket runs: after a
        # mutation-overflow rebuild the shard SHAPES can coincide while
        # the bucket decomposition differs, and the traced per-bucket
        # loops would silently read the wrong rows on a stale cache hit
        key = (spec.algo, spec.variant, spec.exec_mode, static_iters,
               batch, guard, schedule, telemetry,
               tuple(sorted(params.items())),
               (g.n, g.n_orig, g.parts, g.n_local, g.e_max),
               g.layout_signature(),
               (tuple(self.mesh.shape.items()), self.mesh.devices.shape),
               (self.layout, localops.get_mode()))
        hit = self._cache.get(key)
        if hit is not None:
            return hit

        prog = spec.build(g, **params)
        n_inputs = len(spec.inputs)
        kinds = spec.input_kinds
        wire = obs_telemetry.WireRecord() if telemetry else None

        def fn(garr, *inputs):
            garr = {k: v[0] for k, v in garr.items()}
            inputs = tuple(x[0] if kind != "scalar" else x
                           for x, kind in zip(inputs, kinds))
            # the fault context is entered INSIDE the traced fn so taps
            # see the schedule at trace time (it's part of the cache
            # key); same for the telemetry wire recording — a retrace
            # re-fills the SAME record (recording clears on entry)
            cm = faults_mod.active(schedule, detect=guard) \
                if schedule is not None else contextlib.nullcontext()
            tcm = obs_telemetry.recording(wire) if telemetry \
                else contextlib.nullcontext()
            ok = series = None
            with cm, tcm:
                if guard or telemetry:
                    res = run_program(prog, garr, *inputs, guard=guard,
                                      telemetry=telemetry)
                    outs, rounds = res[0], res[1]
                    if guard:
                        ok = res[2]
                    if telemetry:
                        series = res[-1]
                elif batch is None:
                    outs, rounds = run_program(prog, garr, *inputs,
                                               static_iters=static_iters)
                else:
                    outs, rounds = run_program_batched(
                        prog, garr, *inputs, static_iters=static_iters)
            shaped = tuple(o[None] if is_v else o
                           for o, is_v in zip(outs, prog.output_is_vertex))
            tail = (rounds,) + ((ok.astype(jnp.int32),) if guard else ()) \
                + ((series,) if telemetry else ())
            return shaped + tail

        vspec = P("parts", None) if batch is None else P("parts", None, None)
        out_specs = tuple(vspec if is_v else P()
                          for is_v in prog.output_is_vertex) \
            + ((P(), P()) if guard else (P(),)) \
            + ((P(),) if telemetry else ())
        in_specs = (_graph_specs(g, self.layout),) + tuple(
            P() if kind == "scalar" else P("parts", None) for kind in kinds)
        jitted = jax.jit(jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

        root_shape = () if batch is None else (batch,)
        abstract_args = (g.abstract_arrays(self.layout),) + tuple(
            jax.ShapeDtypeStruct(
                root_shape if kind == "scalar" else (g.parts, g.n_local),
                _KIND_DTYPE[kind])
            for kind in kinds)
        compiled = CompiledProgram(spec, prog, jitted, abstract_args,
                                   guarded=guard, faults=schedule,
                                   telemetry=telemetry, wire=wire)
        self._cache[key] = compiled
        return compiled

    # -- thin legacy wrappers -----------------------------------------------
    def bfs(self, mode: str = "fast", max_levels: int = 64,
            static_iters: int = 0) -> CompiledProgram:
        return self.program("bfs", mode, static_iters=static_iters,
                            max_levels=max_levels)

    def pagerank(self, mode: str = "fast", iters: int = 50,
                 tol: float = 1e-6, compress=True,
                 static_iters: int = 0) -> CompiledProgram:
        params = {"iters": iters, "tol": tol}
        if mode == "fast":
            params["compress"] = compress
        return self.program("pagerank", mode, static_iters=static_iters,
                            **params)

    def sssp(self, max_rounds: int = 64,
             static_iters: int = 0) -> CompiledProgram:
        return self.program("sssp", static_iters=static_iters,
                            max_rounds=max_rounds)

    def cc(self, max_rounds: int = 64,
           static_iters: int = 0) -> CompiledProgram:
        return self.program("cc", static_iters=static_iters,
                            max_rounds=max_rounds)

    # -- helpers -------------------------------------------------------------
    def device_graph(self):
        with annotate("engine.upload"):
            arrs = self.g.device_arrays(self.layout)
            sh = jax.sharding.NamedSharding(self.mesh, P("parts", None))
            return {k: jax.device_put(v, sh) for k, v in arrs.items()}

    def gather_vertex_field(self, arr) -> np.ndarray:
        """(P, n_local) sharded -> (n_orig,) numpy."""
        return np.asarray(arr).reshape(-1)[: self.g.n_orig]

    def scatter_vertex_field(self, arr, dtype=None) -> jax.Array:
        """(n_orig,) host values -> (P, n_local) device vertex field,
        sharded like the device-graph arrays (the inverse of
        ``gather_vertex_field``; how warm/cold seeds reach seeded
        programs).  The padded tail is zero-filled — seeded inits
        re-normalize it, since padded vertices are edgeless."""
        g = self.g
        a = np.asarray(arr)
        if a.ndim != 1 or a.shape[0] < g.n_orig:
            raise ValueError(
                f"vertex field must be 1-D with >= n_orig={g.n_orig} "
                f"entries, got shape {a.shape}")
        dt = np.dtype(dtype) if dtype is not None else a.dtype
        full = np.zeros((g.n,), dt)
        full[: g.n_orig] = a[: g.n_orig]
        sh = jax.sharding.NamedSharding(self.mesh, P("parts", None))
        return jax.device_put(full.reshape(g.parts, g.n_local), sh)

    def gather_batched_vertex_field(self, arr) -> np.ndarray:
        """(P, B, n_local) batched sharded -> (B, n_orig) numpy."""
        a = np.asarray(arr)                       # (P, B, n_local)
        b = a.transpose(1, 0, 2).reshape(a.shape[1], -1)
        return b[:, : self.g.n_orig]
