"""Distributed triangle counting via rotated bit-packed neighbor-set
exchange — the first "full NWGraph set" algorithm beyond the traversal /
fixpoint families.

Semantics: triangles of the SIMPLE UNDIRECTED graph underlying the edge
list (parallel edges deduplicated, self-loops dropped) — the standard
convention, and what the NumPy oracle (``tests/oracle.py``) computes.

Adaptation notes: the classical distributed algorithm ships each
vertex's sorted neighbor list to its neighbors and intersects at the
receiver.  The SPMD/static-shape analogue represents a sorted neighbor
SET as a bit-packed row ((n/32,) uint32 — the same wire format as the
``bfs/fast`` frontier), so "intersection of sorted neighbor exchanges"
becomes AND+popcount.  Each superstep ``ppermute``-rotates the packed
adjacency block one partition to the left, so after P rounds every
partition has intersected its rows against every other partition's rows
— P supersteps, each moving n*n_local/8 bytes, no all-to-all.  The
intersection itself is evaluated as a masked dense matmul (unpack both
blocks to f32, one (n_local, n) x (n, n_local) contraction per round):
on TPU this is the MXU-friendly spelling of AND+popcount.

The per-partition adjacency bitmap is O(n^2 / P) memory: right for the
paper's benchmark scales, and the honest roofline story at 2^25
vertices (``ProgramSpec.n_budget`` keeps the launcher from running it
on graphs where the bitmap doesn't fit; the dry-run still lowers it to
price the layout).

Counting: with A the symmetric 0/1 adjacency,
``2 * tri(u) = sum_v A[u, v] * (A @ A)[u, v]`` and the global count is
``sum_u tri(u) / 3``.  Rounds past P are gated no-ops, so the program is
safe under the driver's fixed-trip ``static_iters`` scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.partitioned import AXIS, _tap, psum_scalar
from repro.core.superstep import SuperstepProgram


def _pack_rows(dense_u8):
    """(m, n) uint8 0/1 -> (m, n/32) uint32 bit rows."""
    m, n = dense_u8.shape
    w = dense_u8.reshape(m, n // 32, 32).astype(jnp.uint32)
    return (w << jnp.arange(32, dtype=jnp.uint32)).sum(axis=2,
                                                       dtype=jnp.uint32)


def _unpack_rows(bits, n):
    """(m, n/32) uint32 -> (m, n) f32 0/1 rows."""
    idx = jnp.arange(n)
    words = bits[:, idx >> 5]                       # (m, n)
    return ((words >> (idx & 31).astype(jnp.uint32)) & 1).astype(jnp.float32)


def _sym_adjacency_bits(g, n, n_local):
    """Bit-packed symmetric dedup'd adjacency rows of the local vertices.

    Row u_local holds the neighbor SET {v : u->v or v->u}, self-loops
    excluded; the bitmap is the deduplication (parallel edges set the
    same bit).
    """
    lo = jax.lax.axis_index(AXIS) * n_local
    dense = jnp.zeros((n_local, n + 1), jnp.uint8)  # slop col for sentinel
    srcl, dst = g["out_src_local"], g["out_dst_global"]
    keep = (dst < n) & (dst != srcl + lo)
    dense = dense.at[srcl, jnp.where(keep, dst, n)].max(jnp.uint8(1))
    src, dstl = g["in_src_global"], g["in_dst_local"]
    keep_in = (src < n) & (src != dstl + lo)
    dense = dense.at[dstl, jnp.where(keep_in, src, n)].max(jnp.uint8(1))
    return _pack_rows(dense[:, :n])


def triangles_program(n: int, n_local: int) -> SuperstepProgram:
    """Rotation triangle counting as a superstep program.

    Outputs: per-vertex triangle counts (vertex field) and the global
    triangle total (replicated scalar).  Runs exactly P supersteps.
    """
    parts = n // n_local

    def prepare(g):
        g = dict(g)
        g["adj_bits"] = _sym_adjacency_bits(g, n, n_local)
        return g

    def init(g, *_):
        return g["adj_bits"], jnp.zeros((n_local,), jnp.float32), jnp.int32(0)

    def step(g, state):
        block, tri2, r = state
        p = jax.lax.axis_size(AXIS)
        # round r holds the block of partition q = (me - r) mod P
        q = (jax.lax.axis_index(AXIS) - r) % p
        a = _unpack_rows(g["adj_bits"], n)          # (n_local, n) my rows
        b = _unpack_rows(block, n)                  # (n_local, n) q's rows
        common = a @ b.T                            # |N(u) ^ N(v)| for v in q
        gate = jax.lax.dynamic_slice_in_dim(a, q * n_local, n_local, axis=1)
        contrib = (gate * common).sum(axis=1)
        tri2 = tri2 + jnp.where(r < p, contrib, 0.0)  # no-op past P rounds
        block = jax.lax.ppermute(
            _tap("perm", block, AXIS), AXIS,
            [(i, (i + 1) % p) for i in range(p)])
        return block, tri2, r + 1

    def outputs(state):
        _, tri2, _ = state
        tri = (tri2 / 2.0).astype(jnp.int32)
        total = (psum_scalar(tri2.sum()) / 6.0 + 0.5).astype(jnp.int32)
        return tri, total

    def guard(g, prev, state):
        # per-vertex double-counts accumulate non-negative intersection
        # contributions: finite and non-decreasing.  The rotated
        # adjacency block itself is bitmap data — transport CRC
        # territory, no value invariant to check.
        tri2, ptri2 = state[1], prev[1]
        return jnp.isfinite(tri2).all() & (tri2 >= ptri2).all()

    return SuperstepProgram(
        name="triangles", variant="default", inputs=(),
        prepare=prepare, init=init, step=step,
        halt=lambda state: state[2] >= parts,
        outputs=outputs,
        output_names=("triangles", "total"),
        output_is_vertex=(True, False),
        max_rounds=parts, guard=guard)
