"""The fault-tolerance gate: deterministic fault injection, guard
detection, superstep checkpointing, and rollback recovery.

In-process tier-1 coverage runs at parts=1: schedule parsing, the
``guard=True`` engine path (bit-identity, detection, the two channels),
and the :class:`CheckpointRunner` contracts (checkpoint/resume
bit-identity, recovery, the ``max_recoveries`` bound).

The CHAOS LANE (``-m chaos``, subprocess with forced host devices) is
the acceptance sweep: EVERY registered (algo, variant) pair at parts
{2, 4} runs under a seeded schedule carrying at least one drop, one
corruption and one stall; each run must detect the faults, recover from
the last checkpoint, produce outputs BIT-IDENTICAL to an uninterrupted
direct ``engine.program()`` call, and pass the NumPy oracle
(``tests/oracle.py``; pagerank within its documented tolerance).  The
same sweep pins checkpoint/resume bit-identity for every pair.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_with_devices

import oracle  # noqa: F401  (fail fast if the oracle module breaks)
from repro.core import CheckpointRunner, GraphEngine, RecoveryError, \
    partition_graph, registry
from repro.core.faults import FaultEvent, FaultSchedule, as_schedule
from repro.graphs import urand_edges
from repro.launch.mesh import make_graph_mesh

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

N = 256
ROOT = 3


@pytest.fixture(scope="module")
def eng():
    edges = urand_edges(N, 2048, seed=11)
    g = partition_graph(edges, N, parts=1)
    return GraphEngine(g, make_graph_mesh(1))


# -- schedule plumbing ---------------------------------------------------


def test_fault_event_validation():
    ev = FaultEvent(round=3, part=1, kind="stall", op="min", rounds=2)
    assert ev.spec() == "stall@r3p1:minx2"
    with pytest.raises(ValueError):
        FaultEvent(round=1, part=0, kind="fizzle")
    with pytest.raises(ValueError):
        FaultEvent(round=1, part=0, kind="drop", op="gossip")
    with pytest.raises(ValueError):
        FaultEvent(round=-1, part=0, kind="drop")
    with pytest.raises(ValueError):
        FaultEvent(round=1, part=0, kind="stall", rounds=0)


def test_fault_schedule_parse_roundtrip():
    text = "drop@r1p0 corrupt@r2p1:min stall@r3p0x2 seed=7"
    sched = FaultSchedule.parse(text)
    assert sched.seed == 7 and len(sched.events) == 3
    assert sched.spec() == text
    assert FaultSchedule.parse(sched.spec()) == sched
    assert hash(sched) == hash(FaultSchedule.parse(text))  # cache-keyable
    with pytest.raises(ValueError):
        FaultSchedule.parse("drop@round1part0")


def test_as_schedule_coercion():
    assert as_schedule(None) is None
    sched = FaultSchedule.parse("dup@r0p0 seed=1")
    assert as_schedule(sched) is sched
    assert as_schedule("dup@r0p0 seed=1") == sched
    with pytest.raises(TypeError):
        as_schedule(42)


# -- the guarded engine path ---------------------------------------------


def test_guarded_run_is_bit_identical_and_ok(eng):
    garr = eng.device_graph()
    plain = eng.program("bfs", "fast")
    parents, rounds = plain(garr, jnp.int32(ROOT))
    guarded = eng.program("bfs", "fast", guard=True)
    gparents, grounds, ok = guarded(garr, jnp.int32(ROOT))
    assert int(ok) == 1 and int(grounds) == int(rounds)
    np.testing.assert_array_equal(np.asarray(parents),
                                  np.asarray(gparents))
    # cache identity: (guard, faults) are part of the compile-cache key
    assert eng.program("bfs", "fast", guard=True) is guarded
    assert eng.program("bfs", "fast") is plain and guarded is not plain


@pytest.mark.parametrize("spec", ["corrupt@r1p0:min seed=3",
                                  "drop@r1p0 seed=3",
                                  "stall@r1p0x2 seed=3",
                                  "dup@r1p0 seed=3"])
def test_engine_flags_stamped_faults(eng, spec):
    """Every stamped fault kind lands in the trailing ``ok`` scalar."""
    garr = eng.device_graph()
    prog = eng.program("bfs", "fast", guard=True, faults=spec)
    *_, ok = prog(garr, jnp.int32(ROOT))
    assert int(ok) == 0


def test_clean_schedule_rounds_beyond_halt_stay_ok(eng):
    """An event addressed past the program's last executed round never
    fires and never taints the verdict."""
    garr = eng.device_graph()
    prog = eng.program("bfs", "fast", guard=True,
                       faults="corrupt@r500p0 seed=3")
    *_, ok = prog(garr, jnp.int32(ROOT))
    assert int(ok) == 1


def test_stale_is_transport_silent_on_async(eng):
    """``stale`` (partial delivery) is deliberately NOT stamped: the
    stale-tolerant async variants absorb it — same fixed point, clean
    verdict — which is exactly the fault class they exist for."""
    garr = eng.device_graph()
    clean = eng.program("bfs", "async")
    parents, _ = clean(garr, jnp.int32(ROOT))
    prog = eng.program("bfs", "async", guard=True,
                       faults="stale@r1p0 seed=5")
    sparents, _, ok = prog(garr, jnp.int32(ROOT))
    assert int(ok) == 1
    np.testing.assert_array_equal(np.asarray(parents),
                                  np.asarray(sparents))


def test_value_guard_catches_nan_without_fault_harness():
    """The second detection channel is independent of the fault taps: a
    program whose OWN step writes NaN into float state trips the default
    finite-state screen with no schedule armed at all."""
    from jax import shard_map
    from repro.core.superstep import SuperstepProgram, run_program

    P = jax.sharding.PartitionSpec
    mesh = make_graph_mesh(1)

    def make(poison_round):
        return SuperstepProgram(
            name="probe", variant="nan", inputs=(),
            init=lambda g: (jnp.zeros(8, jnp.float32), jnp.int32(0)),
            step=lambda g, s: (
                jnp.where(s[1] + 1 == poison_round,
                          jnp.full(8, jnp.nan, jnp.float32), s[0] + 1.0),
                s[1] + 1),
            halt=lambda s: s[1] >= 6,
            outputs=lambda s: (s[0],),
            output_names=("x",), output_is_vertex=(True,),
            max_rounds=8)

    def run(prog):
        fn = shard_map(lambda: run_program(prog, {}, guard=True),
                       mesh=mesh, in_specs=(),
                       out_specs=((P("parts"),), P(), P()),
                       check_vma=False)
        (x,), rounds, ok = jax.jit(fn)()
        return np.asarray(x), int(rounds), int(ok)

    _, rounds, ok = run(make(poison_round=99))       # never fires
    assert ok == 1 and rounds == 6
    _, rounds, ok = run(make(poison_round=3))
    assert ok == 0 and rounds == 3                   # stopped at detection


def test_guard_and_faults_validation(eng):
    with pytest.raises(ValueError):
        eng.program("pagerank", "bsp", guard=True, static_iters=4)
    with pytest.raises(ValueError):
        eng.program("bfs", "fast", guard=True, batch=4)
    with pytest.raises(ValueError):
        eng.program("bfs", "fast", faults="drop@r1p0", batch=4)


# -- checkpoint / resume / recovery (parts=1 fast path) ------------------


def _fields(eng, prog, outs):
    names = prog.output_names
    isv = prog.output_is_vertex
    return {n: (eng.gather_vertex_field(o) if v else np.asarray(o))
            for n, o, v in zip(names, outs, isv)}


def test_checkpoint_runner_bit_identity_and_resume(eng):
    garr = eng.device_graph()
    direct = eng.program("bfs", "fast")
    parents, rounds = direct(garr, jnp.int32(ROOT))
    runner = CheckpointRunner(eng, "bfs", "fast", checkpoint_every=2,
                              keep_history=True)
    rep = runner.run(garr, jnp.int32(ROOT))
    assert rep.recoveries == 0 and rep.rounds == int(rounds)
    assert rep.checkpoints == len(rep.history) >= 2
    np.testing.assert_array_equal(
        eng.gather_vertex_field(rep.outputs[0]),
        eng.gather_vertex_field(np.asarray(parents)))
    # resume from a mid-run snapshot: same bits as the full run
    mid = rep.history[len(rep.history) // 2]
    rep2 = runner.run(garr, jnp.int32(ROOT), resume_from=mid)
    assert rep2.recoveries == 0
    np.testing.assert_array_equal(rep.outputs[0], rep2.outputs[0])


def test_checkpoint_runner_recovers_to_clean_bits(eng):
    garr = eng.device_graph()
    direct = eng.program("bfs", "fast")
    parents, _ = direct(garr, jnp.int32(ROOT))
    runner = CheckpointRunner(eng, "bfs", "fast", checkpoint_every=2,
                              faults="corrupt@r2p0:min seed=7")
    rep = runner.run(garr, jnp.int32(ROOT))
    assert rep.recoveries >= 1 and len(rep.detections) >= 1
    np.testing.assert_array_equal(
        eng.gather_vertex_field(rep.outputs[0]),
        eng.gather_vertex_field(np.asarray(parents)))


def test_max_recoveries_bounds_the_rollback_loop(eng):
    garr = eng.device_graph()
    runner = CheckpointRunner(eng, "bfs", "fast", checkpoint_every=2,
                              faults="drop@r1p0 seed=1", max_recoveries=0)
    with pytest.raises(RecoveryError):
        runner.run(garr, jnp.int32(ROOT))


def test_checkpoint_every_validation(eng):
    with pytest.raises(ValueError):
        CheckpointRunner(eng, "bfs", "fast", checkpoint_every=0)


# -- the chaos acceptance sweep (multi-partition, subprocess) ------------

_CHAOS_SWEEP_CODE = """
import sys
sys.path.insert(0, {tests_dir!r})
import numpy as np
import jax.numpy as jnp
import oracle
from repro.core import CheckpointRunner, GraphEngine, incremental, \\
    partition_graph, registry
from repro.launch.mesh import make_graph_mesh

parts, n, seed, root = {parts}, {n}, {seed}, {root}
edges, n = oracle.family_edges("urand", n, seed)
g = partition_graph(edges, n, parts)
eng = GraphEngine(g, make_graph_mesh(parts))
garr = eng.device_graph()
for algo, variant in registry.available():
    spec = registry.get_spec(algo, variant)
    params = oracle.CONFORMANCE_PARAMS.get((algo, variant), {{}})
    if any(k != "scalar" for k in spec.input_kinds):
        (seed_arr,) = incremental.cold_seed(spec, g)
        ins = (eng.scatter_vertex_field(
            seed_arr, incremental.KIND_DTYPES[spec.input_kinds[0]]),)
    else:
        ins = (jnp.int32(root),) * len(spec.inputs)
    # 1) the uninterrupted reference: a direct engine.program() call
    prog = eng.program(algo, variant, **params)
    *outs, rounds = prog(garr, *ins)
    p = prog.program
    ref = [np.asarray(o) for o in outs]

    def check(tag, outputs):
        for name, r, o, isv in zip(p.output_names, ref, outputs,
                                   p.output_is_vertex):
            a = eng.gather_vertex_field(r) if isv else np.asarray(r)[()]
            b = eng.gather_vertex_field(o) if isv else np.asarray(o)[()]
            assert np.array_equal(a, b), (
                f"{{algo}}/{{variant}} parts={{parts}} {{tag}}: output "
                f"{{name}} diverged from the uninterrupted run")

    # 2) checkpointed execution is bit-identical, and so is a resume
    #    from a mid-run snapshot
    runner = CheckpointRunner(eng, algo, variant, checkpoint_every=2,
                              keep_history=True, **params)
    rep = runner.run(garr, *ins)
    assert rep.recoveries == 0, (algo, variant)
    check("checkpointed", rep.outputs)
    mid = rep.history[len(rep.history) // 2]
    rep2 = runner.run(garr, *ins, resume_from=mid)
    check("resumed", rep2.outputs)

    # 3) chaos: >=1 drop + >=1 corruption + >=1 stall inside the
    #    executed-round window; the run must detect, recover from the
    #    last checkpoint, and still produce the uninterrupted bits
    R = max(int(rep.rounds), 1)
    r1, r2, r3 = min(1, R - 1), min(2, R - 1), min(3, R - 1)
    sched = (f"drop@r{{r1}}p0 corrupt@r{{r2}}p{{min(1, parts - 1)}} "
             f"stall@r{{r3}}p0x2 seed=7")
    chaos = CheckpointRunner(eng, algo, variant, checkpoint_every=2,
                             faults=sched, **params)
    rep3 = chaos.run(garr, *ins)
    assert rep3.recoveries >= 1 and rep3.detections, (
        f"{{algo}}/{{variant}} parts={{parts}}: schedule {{sched!r}} "
        f"was never detected")
    check("recovered", rep3.outputs)
    fields = {{name: (eng.gather_vertex_field(o) if isv
                      else np.asarray(o)[()])
               for name, o, isv in zip(p.output_names, rep3.outputs,
                                       p.output_is_vertex)}}
    oracle.check_conformance(algo, variant, fields, edges, n, root)
    print(f"PASS {{algo}}/{{variant}} parts={{parts}} "
          f"recoveries={{rep3.recoveries}}")
print("CHAOS-OK parts=%d" % parts)
"""


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("parts", [2, 4])
def test_chaos_conformance_sweep(parts):
    """Acceptance: every registered pair, seeded drop+corrupt+stall,
    detect -> rollback -> bit-identical outputs -> oracle-exact."""
    out = run_with_devices(
        _CHAOS_SWEEP_CODE.format(tests_dir=TESTS_DIR, parts=parts,
                                 n=N, seed=5, root=ROOT),
        devices=parts, timeout=1200)
    for algo, variant in registry.available():
        assert f"PASS {algo}/{variant} parts={parts}" in out, (
            f"chaos cell missing: {algo}/{variant} parts={parts}\n{out}")
    assert f"CHAOS-OK parts={parts}" in out
