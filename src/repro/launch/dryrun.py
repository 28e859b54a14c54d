import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-lower + compile every (architecture x input
shape) cell against the production mesh, print memory/cost analysis, and
write roofline artifacts.

Runs with 512 placeholder host devices (the two lines above MUST precede
any other import -- JAX locks the device count on first init).

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --arch all --mesh both --out artifacts/dryrun
  python -m repro.launch.dryrun --graph urand28      # paper-side engine
"""

import argparse
import json
import pathlib
import time
import traceback


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir, *,
             impl: str = "chunked", save_hlo: bool = False) -> dict:
    import jax

    from repro.configs.registry import get_arch, get_shape
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import lower_cell
    from repro.roofline import analysis as RA

    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    devices = mesh.size

    t0 = time.time()
    lowered, meta = lower_cell(cfg, shape, mesh, impl=impl)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print(f"[{arch} x {shape_name} x {mesh_name}] {meta['program']}")
    print(f"  memory_analysis: {mem}")
    ca = compiled.cost_analysis()
    print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
          f"bytes={ca.get('bytes accessed', 0):.3e}")

    roof = RA.analyze(
        compiled, arch=arch, shape_name=shape_name, mesh_name=mesh_name,
        devices=devices,
        model_flops_total=RA.model_flops(cfg, shape))
    rec = roof.to_json()
    rec.update({
        "program": meta["program"],
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "arg_bytes_per_device": mem.argument_size_in_bytes,
        "temp_bytes_per_device": mem.temp_size_in_bytes,
        "out_bytes_per_device": mem.output_size_in_bytes,
        "status": "ok",
    })
    hbm = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes) / 1e9
    print(f"  per-device HBM: {hbm:.2f} GB "
          f"(args {mem.argument_size_in_bytes/1e9:.2f} + "
          f"temps {mem.temp_size_in_bytes/1e9:.2f}) "
          f"| bottleneck: {roof.bottleneck} "
          f"(c={roof.compute_s*1e3:.1f}ms m={roof.memory_s*1e3:.1f}ms "
          f"x={roof.collective_s*1e3:.1f}ms) "
          f"useful-flops={roof.useful_flops_ratio:.2f}")

    if out_dir:
        out_dir = pathlib.Path(out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        name = f"{arch}__{shape_name}__{mesh_name}"
        (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
        if save_hlo:
            (out_dir / f"{name}.hlo.txt").write_text(compiled.as_text())
    return rec


def run_graph_dryrun(graph_name: str, mesh_name: str, out_dir) -> list[dict]:
    """Dry-run the paper's graph engine (BFS + PageRank) on the mesh."""
    from repro.core.dryrun import lower_graph_programs

    return lower_graph_programs(graph_name, mesh_name, out_dir)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--graph", default=None,
                    help="run the graph-engine dry-run for this workload")
    ap.add_argument("--impl", default="chunked")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    if args.graph:
        for m in (["pod", "multipod"] if args.mesh == "both" else [args.mesh]):
            run_graph_dryrun(args.graph, m, args.out)
        return

    from repro.configs.base import shapes_for
    from repro.configs.registry import ARCHS, get_arch

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        cfg = get_arch(arch)
        shape_names = ([s.name for s in shapes_for(cfg)]
                       if args.shape == "all" else [args.shape])
        for shape_name in shape_names:
            for mesh_name in meshes:
                try:
                    run_cell(arch, shape_name, mesh_name, args.out,
                             impl=args.impl, save_hlo=args.save_hlo)
                except Exception as e:  # noqa: BLE001 - report and continue
                    traceback.print_exc()
                    failures.append((arch, shape_name, mesh_name, repr(e)[:200]))
                    if args.out:
                        out = pathlib.Path(args.out)
                        out.mkdir(parents=True, exist_ok=True)
                        name = f"{arch}__{shape_name}__{mesh_name}"
                        (out / f"{name}.json").write_text(json.dumps(
                            {"arch": arch, "shape": shape_name,
                             "mesh": mesh_name, "status": "fail",
                             "error": repr(e)[:500]}, indent=2))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nAll dry-run cells passed.")


if __name__ == "__main__":
    main()
