"""Chip benchmark of the graph engine: one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload kron21.bfs --seed 7 --seconds 40 --trace 0

Set-up builds the cell's graph from the seed, partitions it with the
program's host build, compiles the algorithm's default variant and warms
it up with one launch.  The window then runs whole launches back to back
until ``--seconds`` have passed; each ends in ``block_until_ready``.
After the window every launch is checked against the NumPy reference in
``programs/`` and the metrics are read by the readers in ``metrics/``.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<name>.json`` (with ``generators/<generator>.py``),
``traffic/<name>.json`` (with ``programs/<program>.py``) and
``metrics/<name>.py``.

The last line of stdout is the JSON result; the numbers compared, each
beside its limit, are the last lines of stderr and the result's last
key.  With no TPU, or fewer chips than the cell asks for, the command
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / "artifacts" / "trace"

sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

import xplane  # noqa: E402


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def _named(kind: str, name: str, suffix: str) -> Path:
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        raise LookupError(f"no {kind} named {name!r} ({path})")
    return path


def load_json(kind: str, name: str) -> dict:
    return json.loads(_named(kind, name, ".json").read_text())


def load_module(kind: str, name: str):
    path = _named(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_plan(name: str, bench: dict | None = None) -> dict:
    """The cell, its configuration and traffic, and the metrics it
    reports with the profiler off (``end_to_end``) and on
    (``per_layer``)."""
    bench = bench or manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LookupError(f"no workload named {name!r}")
    cell = cells[name]
    return {
        "cell": cell,
        "config": load_json("configs", cell["config"]),
        "traffic": load_json("traffic", cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"]
                       if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise LookupError(f"no peaks for device kind {kind!r} in "
                          "peaks.json")
    return table[kind]


def require_tpu(chips: int):
    """The first ``chips`` TPU devices and their peaks; exits non-zero
    without a result when JAX finds no TPU, too few of them, or a kind
    that ``peaks.json`` does not list."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX found {devices[0].platform})")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found "
                 f"{len(devices)}")
    try:
        peaks = peaks_for(devices[0].device_kind)
    except LookupError as e:
        sys.exit(f"bench: {e}")
    return devices, peaks


def device_peak(devices) -> int:
    """The allocator's ``peak_bytes_in_use`` on the fullest of
    ``devices`` since the process started (0 where it keeps none)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def host_peak_gib() -> float:
    """The process's peak resident memory so far, in GiB."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for every program however quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def note(msg: str) -> None:
    """A progress line on stderr, stamped with seconds since start."""
    print(f"{time.perf_counter() - T_START:8.2f}s {msg}", file=sys.stderr,
          flush=True)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"{xplane.SPAN_PREFIX}{name}")


def make_graph(cfg: dict, seed: int):
    """The configuration's graph: its generator's structure from the
    fixed ``structure_seed``, with vertex labels permuted from ``seed``
    (so every seed runs the same graph up to isomorphism, and the same
    program shapes).  Returns (edges (M, 2), arcs (2M, 2), n, perm)."""
    import jax
    gen = load_module("generators", cfg["generator"])
    n = 1 << cfg["scale"]
    perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
    # (2, M) on the device: an (M, 2) int32 array is tiled 64x there
    relabel = jax.jit(lambda key, p: p[gen.edges(key, cfg)])
    uv = np.asarray(relabel(jax.random.key(cfg["structure_seed"]), perm))
    edges = np.ascontiguousarray(uv.T)
    arcs = np.concatenate([edges, edges[:, ::-1]])
    return edges, arcs, n, perm


@dataclass
class Run:
    """What the metric readers read."""
    algo: str
    n: int
    arcs: int
    setup_s: float
    partition_s: float
    compile_s: float
    window_s: float
    launches: list = field(default_factory=list)  # rounds, work
    peak_bytes: int | None = None
    peaks: dict = field(default_factory=dict)
    trace: xplane.Reduced | None = None


def execute(plan: dict, seed: int, seconds: float, trace: bool,
            peaks: dict, control: bool = False) -> dict:
    """Set up, run the window, check it and read the metrics; returns
    the result record (without its ``device`` entry).

    ``control=True`` puts the answers of the program module's
    ``control`` function in place of what the window produced
    (``control.py``; the benchmark's runs never do)."""
    import jax
    from repro.core import GraphEngine, partition_graph
    from repro.launch.mesh import make_graph_mesh

    cell, cfg, traffic = plan["cell"], plan["config"], plan["traffic"]
    program = load_module("programs", traffic["program"])
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # host spans and device operations; no Python call tracing
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    with span("generate"):
        edges, arcs, n, perm = make_graph(cfg, seed)
        launch_inputs = program.inputs(traffic, cfg, edges, perm)
    note(f"generated {len(edges):,} edges; device peak so far "
         f"{device_peak(jax.local_devices()):,} B; host peak RSS "
         f"{host_peak_gib():.2f} GiB")
    t0 = time.perf_counter()
    with span("partition"):
        g = partition_graph(arcs, n, cfg["parts"])
    partition_s = time.perf_counter() - t0
    note(f"partitioned in {partition_s:.3f}s; host peak RSS "
         f"{host_peak_gib():.2f} GiB")
    n_arcs = len(arcs)
    del arcs
    eng = GraphEngine(g, make_graph_mesh(cfg["parts"]))
    with span("upload"):
        garr = jax.block_until_ready(eng.device_graph())
    params = program.params(traffic)
    prog = eng.program(program.ALGO, **params)
    print(f"[bench] {cell['name']}: {prog.program.key} (default variant "
          f"of {program.ALGO}), params {params}; n={n:,} arcs={n_arcs:,}",
          flush=True)
    t0 = time.perf_counter()
    with span("compile"):
        prog.lower(garr, *launch_inputs[0]).compile()
    compile_s = time.perf_counter() - t0
    note(f"compiled in {compile_s:.3f}s")
    with span("warmup"):
        jax.block_until_ready(prog(garr, *launch_inputs[0]))
    window_inputs = launch_inputs[1:] or launch_inputs

    timed = []
    setup_s = time.perf_counter() - T_START
    note("warmed up; window starts")
    with span("window"):
        while not timed or timed[-1][3] - timed[0][2] < seconds:
            inp = window_inputs[len(timed) % len(window_inputs)]
            with span("launch"):
                t1 = time.perf_counter()
                out = jax.block_until_ready(prog(garr, *inp))
                t2 = time.perf_counter()
            timed.append((inp, out, t1, t2))
            note(f"launch {len(timed)}: {t2 - t1:.4f}s")
    window_s = timed[-1][3] - timed[0][2]
    peak = device_peak(garr[next(iter(garr))].devices())

    with span("fetch"):
        names = prog.program.output_names
        is_vertex = prog.program.output_is_vertex
        launches = [{"inputs": inp,
                     "outputs": {k: (eng.gather_vertex_field(o) if v
                                     else np.asarray(o))
                                 for k, o, v in zip(names, out, is_vertex)},
                     "rounds": int(out[len(names)])}
                    for inp, out, _, _ in timed]
    del timed, out, garr, prog, eng, g
    gc.collect()
    if control:
        launches = program.control(traffic, edges, n, launches)
    with span("validate"):
        verdict = program.check(traffic, edges, n, launches)
    note("validated")
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        found = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        reduced = xplane.reduce(xplane.load(found[-1])) if found else None

    for rec, w in zip(launches, verdict["work"]):
        rec["work"] = w
        del rec["outputs"]
    run = Run(algo=program.ALGO, n=n, arcs=n_arcs, setup_s=setup_s,
              partition_s=partition_s, compile_s=compile_s,
              window_s=window_s, launches=launches, peak_bytes=peak,
              peaks=peaks, trace=reduced)
    metrics = {}
    for m in plan["per_layer" if trace else "end_to_end"]:
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in verdict["checks"].items()}
    correct = (bool(launches) and verdict["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": len(launches),
              "failed": verdict["failed"], "metrics": metrics,
              "device": {"memory_peak_bytes": peak}}
    if reduced is not None:
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    plan = cell_plan(args.workload)
    devices, peaks = require_tpu(plan["cell"]["chips"])
    enable_compile_cache()
    result = execute(plan, args.seed, args.seconds, bool(args.trace), peaks)
    dev = devices[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), **result["device"]}
    result["checks"] = result.pop("checks")
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
