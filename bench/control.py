"""The control of a cell's comparison, at the cell's own size.

    python3 bench/control.py --workload urand21.pagerank --seeds 11,12,13 --seconds 10

Runs the cell as ``run.py`` does, one seed after another in one process,
with the control of ``programs/<program>.py`` in the program's place,
and prints each seed's numbers compared beside their limits.  Every
seed has to come out not correct.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import json

import run


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    plan = run.cell_plan(args.workload)
    _, peaks = run.require_tpu(plan["cell"]["chips"])
    run.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.execute(plan, seed, args.seconds, False, peaks,
                          control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)


if __name__ == "__main__":
    main()
