#!/usr/bin/env bash
# CI gate: tier-1 test suite + fast benchmark smoke.
#
#   bash scripts/ci.sh             # full suite (tier-1 + slow) + bench
#   bash scripts/ci.sh --markers   # tiered: fast lane first, then slow
#
# The tier split uses the pytest marker `slow` (subprocess / multi-device
# tests).  The oracle-conformance suite is deliberately NOT marked slow:
# it is the correctness gate every registered program must pass, so it
# runs in tier-1 in both modes.  That includes the ASYNC lane — the
# */async variants are registered programs, so they sweep parts
# {1, 2, 4} x three families against the same oracles in tier-1, and
# tests/test_async.py (rounds-accounting + exec_mode plumbing) rides
# the fast lane with them.  The `tier1` marker PINS a suite to the
# fast lane (selected as "tier1 or not slow", so tier1 wins even if a
# suite someday also gets marked slow): the kernel-interpret parity
# suites (tests/test_kernels_{spmv,frontier}.py) carry it because the
# localops dispatch layer's `kernel` mode drives those kernels.  The
# production hot loops take the blocked-ELL gather path on every
# backend: Mosaic does not lower either kernel for a TPU yet.
#
# The fast benches write BENCH_graph.json (direct launches — the bfs
# and pagerank figures emit bsp-vs-async row pairs, each row carrying
# rounds_to_converge + wire_mb_per_part, both gated deterministically),
# BENCH_serve.json (the query-serving path: queries/sec + latency per
# (algo, bucket) cell) and BENCH_mutate.json (the dynamic-graph path:
# in-place mutation apply + warm-vs-cold recompute rounds) at the repo
# root so all three perf trajectories are tracked across PRs, and
# benchmarks/compare.py gates the fresh rows against the committed ones
# (>1.25x wall-time growth or queries/sec drop on any cell fails CI).
# bench_mutate additionally fails outright when the PageRank warm
# restart stops beating the cold start on rounds-to-converge.
#
# The `chaos` marker is the seeded fault-injection acceptance sweep
# (tests/test_chaos.py): every registered (algo, variant) pair at
# parts {2, 4} under a drop+corrupt+stall schedule must detect via its
# guard, recover from the last checkpoint, and match the NumPy oracle
# exactly.  It runs as its own lane in BOTH modes (multi-device
# subprocesses — isolating it keeps the tier-1 signal fast and clean).
#
# The `durability` marker is the crash-recovery acceptance drill
# (tests/test_persist.py): for each named crash point in the
# WAL/snapshot protocol, a subprocess server is killed at that exact
# instruction mid-mutation-trace, recovered in a fresh process, and
# must land on the exact epoch + edge multiset with probe answers
# bit-identical to an uninterrupted reference run.  Like chaos, it is
# its own lane in both modes.
#
# The `obs` marker is the observability acceptance drill
# (tests/test_obs.py): a multi-device subprocess runs a short TRACED
# serve session and schema-validates its exported Chrome trace
# (matched async pairs, ordered tracks, proper nesting), asserts
# telemetry-OFF builds stay bit-identical to the seed path, and runs
# telemetry-ON programs through the NumPy-oracle gate at parts
# {1, 2, 4}.  Its own lane in both modes; the in-process obs unit
# tests (series parsing, span rings, exporter schema, registry drift)
# ride tier-1.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "--markers" ]]; then
    echo "== tier-1: pytest -m 'tier1 or not slow' (fast lane: conformance + kernel parity) =="
    python -m pytest -x -q -m "(tier1 or not slow) and not chaos and not durability and not obs"
    echo "== tier-2: pytest -m 'slow and not tier1' (subprocess / multi-device) =="
    python -m pytest -q -m "slow and not tier1 and not chaos and not durability and not obs"
else
    echo "== tier-1: pytest =="
    python -m pytest -x -q -m "not chaos and not durability and not obs"
fi

echo "== chaos lane: pytest -m chaos (seeded fault-injection sweep, parts {2,4}) =="
python -m pytest -q -m chaos

echo "== durability lane: pytest -m durability (crash-point kill + recovery drills) =="
python -m pytest -q -m durability

echo "== obs lane: pytest -m obs (traced serve + schema-valid trace export + telemetry bit-identity/conformance) =="
python -m pytest -q -m obs

echo "== bench smoke: benchmarks.run --fast =="
python -m benchmarks.run --fast

test -f BENCH_graph.json || { echo "BENCH_graph.json missing" >&2; exit 1; }

echo "== serve bench: benchmarks.bench_serve --fast =="
python -m benchmarks.bench_serve --fast

test -f BENCH_serve.json || { echo "BENCH_serve.json missing" >&2; exit 1; }

echo "== mutate bench: benchmarks.bench_mutate --fast =="
python -m benchmarks.bench_mutate --fast

test -f BENCH_mutate.json || { echo "BENCH_mutate.json missing" >&2; exit 1; }

echo "== bench regression gate: benchmarks.compare (vs committed rows) =="
python -m benchmarks.compare --threshold 1.25

echo "== CI OK =="
