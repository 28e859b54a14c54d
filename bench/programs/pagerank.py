"""PageRank under GAP's rules: whole solves from the uniform start, and
the comparison with a float64 power iteration.

Plain NumPy over the benchmark's own edge list; nothing here comes from
the program.  Dangling vertices (degree 0) give nothing, as in GAP.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

ALGO = "pagerank"


def params(traffic: dict) -> dict:
    """Engine parameters the source fixes: the tolerance and the cap."""
    return {"iters": traffic["max_iters"], "tol": traffic["tol"]}


def inputs(traffic: dict, cfg: dict, edges: np.ndarray,
           perm: np.ndarray) -> list[tuple]:
    """Every solve starts from the uniform vector: no per-launch input."""
    return [()]


def _arcs(edges):
    u = np.concatenate([edges[:, 0], edges[:, 1]])
    v = np.concatenate([edges[:, 1], edges[:, 0]])
    return u, v


def power_iteration(edges: np.ndarray, n: int, damping: float, iters: int,
                    dtype=np.float64) -> np.ndarray:
    """Rank after ``iters`` steps from 1/n, every value rounded to
    ``dtype`` (float64 for the reference, bfloat16 for the control)."""
    u, v = _arcs(edges)
    deg = np.bincount(u, minlength=n).astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)

    def cast(x):
        return np.asarray(x, dtype).astype(np.float64)

    base = cast((1.0 - damping) / n)
    rank = cast(np.full(n, 1.0 / n))
    for _ in range(iters):
        z = cast(np.bincount(v, weights=cast(rank * inv)[u], minlength=n))
        rank = cast(base + cast(damping * z))
    return rank


def residual_l1(edges: np.ndarray, n: int, damping: float,
                rank: np.ndarray) -> float:
    """GAP's PageRank verifier: the L1 change one more power step would
    make to ``rank``, in float64."""
    rank = np.asarray(rank, np.float64)
    u, v = _arcs(edges)
    deg = np.bincount(u, minlength=n).astype(np.float64)
    contrib = np.where(deg > 0, rank / np.maximum(deg, 1.0), 0.0)
    z = np.bincount(v, weights=contrib[u], minlength=n)
    return float(np.abs((1.0 - damping) / n + damping * z - rank).sum())


def max_rel_err(rank: np.ndarray, ref: np.ndarray) -> float:
    """Largest per-vertex error relative to the reference rank (every
    reference rank is at least (1 - damping) / n > 0)."""
    return float((np.abs(np.asarray(rank, np.float64) - ref) / ref).max())


def check(traffic: dict, edges: np.ndarray, n: int,
          launches: list[dict]) -> dict:
    """Each solve against the float64 reference run for as many steps
    as the solve reports, and GAP's residual."""
    d = traffic["damping"]
    limits = traffic["limits"]
    refs = {}
    worst_rel, worst_res, failed = 0.0, 0.0, 0
    for rec in launches:
        steps = int(rec["rounds"])
        if steps not in refs:
            refs[steps] = power_iteration(edges, n, d, steps)
        rank = rec["outputs"]["rank"]
        rel = max_rel_err(rank, refs[steps])
        res = residual_l1(edges, n, d, rank)
        failed += not (rel <= limits["pr_max_rel_err"]
                       and res <= limits["pr_residual_l1"])
        worst_rel, worst_res = max(worst_rel, rel), max(worst_res, res)
    return {"failed": failed, "work": [0] * len(launches),
            "checks": {"pr_max_rel_err": (worst_rel,
                                          limits["pr_max_rel_err"]),
                       "pr_residual_l1": (worst_res,
                                          limits["pr_residual_l1"])}}


def control(traffic: dict, edges: np.ndarray, n: int,
            launches: list[dict]) -> list[dict]:
    """The reference in bfloat16, the precision below the float32 of
    GAP's scores, in the program's place, for the same step counts.
    (The program's own bfloat16 exchange path, ``compress="always"``,
    is no control on one chip: there the exchange is the identity and
    the chip's compiler drops the float32-bfloat16-float32 round trip,
    so it reads as the float32 path does.)"""
    return [{**rec, "outputs": {"rank": power_iteration(
        edges, n, traffic["damping"], int(rec["rounds"]),
        ml_dtypes.bfloat16)}} for rec in launches]
