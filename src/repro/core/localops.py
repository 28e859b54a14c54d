"""Local-ops dispatch: backend-tuned kernels for the superstep work bundle.

"The Anatomy of Large-Scale Distributed Graph Algorithms" separates a
distributed graph algorithm's per-superstep *work bundle* from its
exchange machinery; ``core/partitioned.py`` owns the exchanges, and this
module owns the work bundle.  Every program hot loop routes through one
of five primitives:

  ``spmv_pull(g, ell, x)``
      y[v] = sum over in-neighbors u of v of x[u]  (PageRank pull).
  ``frontier_pull(g, ell, bits, unvisited)``
      min-id in-neighbor of v present in the packed frontier bitmap, or
      INT_INF (owner-side BFS parent derivation).
  ``pull_min_eq(g, ell, xg, target)``
      min-id in-neighbor u of v with xg[u] == target[v] - the
      level-keyed frontier_pull (bfs/async parent derivation).
  ``scatter_combine(g, ell, vals, op, identity=...)``
      combine per-edge values into a per-row accumulator with
      op in {add, min, max, or} - the generalized edge combine.
  ``push_combine(g, ell_in, ell_dst, x, op, identity=...)``
      combine a per-source vertex field over each destination's arcs
      into a length-n accumulator (PageRank, BFS and betweenness push).
      At parts=1 it gathers ``x`` once per ``ell_in`` slot, the same
      loop as ``spmv_pull``; at parts>1 it gathers ``x`` into out-edge
      order and combines through ``ell_dst``.

Each primitive has THREE implementations, selected at trace time:

  * ``ref``     the COO scatter idiom the programs used to inline
                (``.at[...].add/min/max`` over the padded (P, E) edge
                list).  Lowers to serialized scatters on CPU - kept as
                the debugging baseline and the ``--layout coo`` path.
  * ``ell``     dense per-bucket gather + row reduction over the
                blocked-ELL layout (``core/graph.py``): fully vectorized
                on every backend, no scatters anywhere (results return
                to row order through the inverse-permutation GATHER).
  * ``pallas``  the TPU kernels in ``repro/kernels/{spmv,frontier}``,
                applied per ELL bucket (f32 additive combines route
                through the SpMV kernel; frontier tests through the BFS
                pull kernel; non-kernelizable ops stay on the ell path).

Mode resolution: the ``REPRO_LOCALOPS`` env var (or :func:`set_mode`)
picks ``auto`` (default: ell on every backend, the TPU included),
``ref``, or ``kernel`` (the Pallas kernels, interpreted off-TPU).
``auto`` never picks the kernels because Mosaic refuses both for a TPU
("Only 2D gather is supported": each gathers from a whole-array 1-D
VMEM block with a 2-D index); ``kernel`` on a TPU compiles them
natively and lets that error propagate.  When the graph dict carries
no ELL arrays (``--layout coo``), every call falls back to ``ref``
regardless of mode.

All functions are pure per-partition compute (no collectives), callable
inside or outside ``shard_map``, and vmap cleanly for batched
multi-source programs.  The ell path gathers each bucket slot-major, as
a ``(k, rows)`` view with the rows minor, and every gather of a
per-query field goes through :func:`_take`, whose batching rule keeps
the lane axis leading.  vmap's own rule for a batched operand gathered
by shared indices would put the lane axis minor, which a TPU pads to
128 lanes: batch=8 bfs/fast on urand22 then asked for ~60 GB of a
16 GB v5e.

Each primitive runs inside its device scope ``localops.<primitive>``,
each bucket's gather inside ``<ell name>.b<i>`` and the final
inverse-permutation gather inside ``reorder`` (``obs/scopes.py``), so a
profiler trace of the ell path can be read per primitive and bucket;
under ``localops.push_combine`` the bucket names show the route taken
(``ell_in.b<i>`` at parts=1, ``ell_dst.b<i>`` at parts>1).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.graph import EllMeta
from repro.core.partitioned import test_bit
from repro.obs.scopes import device_scope

INT_INF = jnp.int32(2 ** 30)

MODES = ("auto", "ref", "kernel")
_MODE_OVERRIDE: str | None = None

# ref-path metadata: which COO key array feeds each ELL structure, and
# whether that key can carry the sentinel (needs a +1 drop slot)
_COO_KEY = {
    "ell_out": ("out_src_local", False),
    "ell_dst": ("out_dst_global", True),
    "ell_src": ("in_src_global", True),
}


def set_mode(mode: str | None) -> None:
    """Process-wide override of the REPRO_LOCALOPS env var (None clears).

    NOTE: the mode is read at TRACE time; ``GraphEngine.program`` keys
    its compile cache on the active mode so switching re-traces.
    """
    global _MODE_OVERRIDE
    if mode is not None and mode not in MODES:
        raise ValueError(f"localops mode {mode!r} not in {MODES}")
    _MODE_OVERRIDE = mode


def get_mode() -> str:
    """The active dispatch mode: override > $REPRO_LOCALOPS > auto."""
    mode = _MODE_OVERRIDE or os.environ.get("REPRO_LOCALOPS", "auto")
    if mode not in MODES:
        raise ValueError(
            f"REPRO_LOCALOPS={mode!r} invalid; expected one of {MODES}")
    return mode


def resolve(mode: str | None = None, backend: str | None = None) -> str:
    """Concrete implementation a call would take: ref | ell | pallas.

    ``backend`` lets a caller ask about a platform it is not running on
    (``resolve("auto", backend="tpu")``); no backend changes the answer,
    since ``auto`` is ``ell`` on the TPU too (see the module doc)."""
    mode = mode or get_mode()
    if mode == "ref":
        return "ref"
    if mode == "kernel":
        return "pallas"
    return "ell"


def _use_pallas(mode: str) -> bool:
    return resolve(mode) == "pallas"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _has_ell(g: dict, ell: EllMeta) -> bool:
    return f"{ell.name}_idx" in g


# largest flat offset a batched :func:`_take` adds to its int32 indices
_FLAT_LIMIT = 2 ** 31 - 1


@jax.custom_batching.custom_vmap
def _take(x, idx):
    """``x[idx]``: gather a per-query 1-D field by graph indices."""
    return x[idx]


@_take.def_vmap
def _take_vmap(axis_size, in_batched, x, idx):
    """Batched ``x`` (B, N): gather from the flattened (B*N,) field with
    lane offsets added to the indices, so the output is (B, *idx.shape)
    with ``idx``'s minor axis minor (vmap's default rule takes (B, 1)
    slices and lays the lane axis out minor).  Lanes go in chunks whose
    flat offsets fit int32."""
    x_batched, idx_batched = in_batched
    if not x_batched:
        return x[idx], idx_batched
    n = x.shape[1]
    lane_ndim = idx.ndim - 1 if idx_batched else idx.ndim
    per = max(1, _FLAT_LIMIT // n)
    chunks = []
    for b0 in range(0, axis_size, per):
        nb = min(per, axis_size - b0)
        off = (jnp.arange(nb, dtype=jnp.int32) * n).reshape(
            (nb,) + (1,) * lane_ndim)
        lane_idx = idx[b0:b0 + nb] if idx_batched else idx
        chunks.append(x[b0:b0 + nb].reshape(-1)[lane_idx + off])
    return jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0], True


def _test_bit(packed, idx):
    """:func:`~repro.core.partitioned.test_bit` through :func:`_take`."""
    word = _take(packed, idx >> 5)
    return (word >> (idx & 31).astype(jnp.uint32)) & 1


def _buckets(ell: EllMeta, flat):
    """Yield (device scope, row0, rows, width, (rows, width) idx block)
    per bucket; the scope ``<ell name>.b<i>`` names the bucket's work."""
    off = 0
    r0 = 0
    for i, (rows, k) in enumerate(ell.buckets):
        blk = flat[..., off:off + rows * k].reshape(
            flat.shape[:-1] + (rows, k)) if k else None
        yield device_scope(f"{ell.name}.b{i}"), r0, rows, k, blk
        off += rows * k
        r0 += rows


def _reorder(outs, inv):
    """Bucket rows back to row order: the inverse-permutation gather."""
    with device_scope("reorder"):
        return _take(jnp.concatenate(outs), inv)


# reduce a slot-major (k, rows) gather over its slots
_REDUCERS = {
    "add": lambda a: a.sum(axis=0),
    "min": lambda a: a.min(axis=0),
    "max": lambda a: a.max(axis=0),
    "or": lambda a: a.any(axis=0),
}


def _check_op(op: str) -> None:
    if op not in _REDUCERS:
        raise ValueError(f"combine op {op!r} not in {tuple(_REDUCERS)}")


def _combine_slots(g: dict, ell: EllMeta, x, op: str, identity, mode: str):
    """``op``-combine of ``x[slot]`` over each row's slots of ``ell``, in
    row order: the ell path of every gather-and-reduce primitive.

    ``x`` is what the slots index: a vertex field for the neighbor-id
    structure ``ell_in``, per-edge values for an edge-position one.  The
    sentinel (n or E, one past ``x``) reads an appended ``identity``
    slot.  f32 sums take the SpMV kernel in the kernel mode."""
    xk = jnp.concatenate([x, jnp.full((1,), identity, x.dtype)])
    kernel_add = (op == "add" and x.dtype == jnp.float32
                  and _use_pallas(mode))
    outs = []
    for scope, _, rows, k, blk in _buckets(ell, g[f"{ell.name}_idx"]):
        with scope:
            if k == 0:
                outs.append(jnp.full((rows,), identity, x.dtype))
            elif kernel_add:
                from repro.kernels.spmv.kernel import spmv_ell
                vmask = (blk != ell.sentinel).astype(jnp.float32)
                outs.append(spmv_ell(blk, vmask, xk, row_block=128,
                                     interpret=_interpret()))
            else:
                # (k, rows) slot-major, rows minor
                outs.append(_REDUCERS[op](_take(xk, blk.T)))
    return _reorder(outs, g[f"{ell.name}_inv"])


# ---------------------------------------------------------------------------
# spmv_pull
# ---------------------------------------------------------------------------

@device_scope("localops.spmv_pull")
def spmv_pull(g: dict, ell: EllMeta, x, *, mode: str | None = None):
    """y[row] = sum of x[neighbor] over the row's ELL slots, f32.

    ``ell`` must be a neighbor-id structure (``ell_in``): slots hold
    GLOBAL vertex ids, sentinel contributes 0.  The ref path is the COO
    gather + scatter-add over the in-shard.
    """
    mode = mode or get_mode()
    x = x.astype(jnp.float32)
    if mode == "ref" or not _has_ell(g, ell):
        src = g["in_src_global"]
        dstl = g["in_dst_local"]
        valid = src < ell.sentinel
        gathered = jnp.where(valid, x[jnp.where(valid, src, 0)], 0.0)
        return jnp.zeros((ell.n_rows,), jnp.float32).at[dstl].add(
            gathered, mode="drop")
    return _combine_slots(g, ell, x, "add", jnp.float32(0.0), mode)


# ---------------------------------------------------------------------------
# frontier_pull
# ---------------------------------------------------------------------------

@device_scope("localops.frontier_pull")
def frontier_pull(g: dict, ell: EllMeta, bits, unvisited, *,
                  mode: str | None = None):
    """Min-id in-neighbor of each row present in the packed frontier.

    ``bits`` is the (n/32,) uint32 global frontier bitmap; ``unvisited``
    a (n_rows,) bool mask.  Returns (n_rows,) int32, INT_INF where the
    row is visited or has no in-frontier neighbor.  ``ell`` must be the
    neighbor-id structure (``ell_in``).
    """
    mode = mode or get_mode()
    n = ell.sentinel
    if mode == "ref" or not _has_ell(g, ell):
        src = g["in_src_global"]
        dstl = g["in_dst_local"]
        valid = src < n
        hit = test_bit(bits, jnp.where(valid, src, 0)) == 1
        hit = hit & valid & unvisited[dstl]
        return jnp.full((ell.n_rows,), INT_INF, jnp.int32).at[
            jnp.where(hit, dstl, ell.n_rows - 1)].min(
            jnp.where(hit, src, INT_INF), mode="drop")

    idx = g[f"{ell.name}_idx"]
    inv = g[f"{ell.name}_inv"]
    perm = g[f"{ell.name}_perm"]
    unv_ell = _take(unvisited, perm)
    # sentinel n indexes one word past the bitmap: append a zero guard
    bits_g = jnp.concatenate([bits, jnp.zeros((1,), jnp.uint32)])
    use_pallas = _use_pallas(mode)
    outs = []
    for scope, r0, rows, k, blk in _buckets(ell, idx):
        with scope:
            if k == 0:
                outs.append(jnp.full((rows,), INT_INF, jnp.int32))
                continue
            unv_b = unv_ell[r0:r0 + rows]
            if use_pallas:
                from repro.kernels.frontier.kernel import bfs_pull
                outs.append(bfs_pull(blk, bits_g, unv_b.astype(jnp.int32),
                                     row_block=128, interpret=_interpret()))
            else:
                cols = blk.T
                hit = _test_bit(bits_g, cols) == 1
                cand = jnp.where(hit, cols, INT_INF).min(axis=0)
                outs.append(jnp.where(unv_b, cand, INT_INF))
    return _reorder(outs, inv)


# ---------------------------------------------------------------------------
# pull_min_eq
# ---------------------------------------------------------------------------

@device_scope("localops.pull_min_eq")
def pull_min_eq(g: dict, ell: EllMeta, xg, target, *,
                mode: str | None = None):
    """Min-id in-neighbor ``u`` of each row ``v`` with ``xg[u] ==
    target[v]``, or INT_INF when none matches.

    The level-keyed generalization of :func:`frontier_pull`: instead of
    testing membership in one frontier bitmap, each row names the value
    class it wants (``target``, e.g. ``level[v] - 1``) and slots whose
    global field ``xg`` equals it qualify.  bfs/async uses it to derive
    parents from converged levels in ONE pull — every level's parents at
    once, where the bitmap form needs a pass per level.  ``ell`` must be
    the neighbor-id structure (``ell_in``); no Pallas kernel applies, so
    the kernel mode rides the ell path (the module-doc rule for
    non-kernelizable ops).
    """
    mode = mode or get_mode()
    n = ell.sentinel
    if mode == "ref" or not _has_ell(g, ell):
        src = g["in_src_global"]
        dstl = g["in_dst_local"]
        valid = src < n
        hit = valid & (xg[jnp.where(valid, src, 0)] == target[dstl])
        return jnp.full((ell.n_rows,), INT_INF, jnp.int32).at[
            jnp.where(hit, dstl, ell.n_rows - 1)].min(
            jnp.where(hit, src, INT_INF), mode="drop")

    idx = g[f"{ell.name}_idx"]
    inv = g[f"{ell.name}_inv"]
    perm = g[f"{ell.name}_perm"]
    tgt_ell = _take(target, perm)
    # sentinel n indexes one slot past xg: append a guard no real target
    # equals (INT_INF; targets are levels < n or INT_INF - 1 for
    # unreached rows)
    xg_g = jnp.concatenate([xg, jnp.full((1,), INT_INF, xg.dtype)])
    outs = []
    for scope, r0, rows, k, blk in _buckets(ell, idx):
        with scope:
            if k == 0:
                outs.append(jnp.full((rows,), INT_INF, jnp.int32))
                continue
            cols = blk.T
            hit = _take(xg_g, cols) == tgt_ell[r0:r0 + rows][None, :]
            outs.append(jnp.where(hit, cols, INT_INF).min(axis=0))
    return _reorder(outs, inv)


# ---------------------------------------------------------------------------
# scatter_combine
# ---------------------------------------------------------------------------

def _scatter_combine(g, ell, vals, op, identity, mode):
    if mode == "ref" or not _has_ell(g, ell):
        key_name, may_drop = _COO_KEY[ell.name]
        key = g[key_name]
        size = ell.n_rows + (1 if may_drop else 0)
        if op == "or":  # bool OR as the uint8 scatter-max idiom
            acc = jnp.zeros((size,), jnp.uint8).at[key].max(
                vals.astype(jnp.uint8))
            return acc[:ell.n_rows] > 0
        acc = jnp.full((size,), identity, vals.dtype)
        acc = getattr(acc.at[key], op)(vals)
        return acc[:ell.n_rows]
    return _combine_slots(g, ell, vals, op, identity, mode)


@device_scope("localops.scatter_combine")
def scatter_combine(g: dict, ell: EllMeta, vals, op: str, *, identity,
                    mode: str | None = None):
    """Combine per-edge ``vals`` into a (n_rows,) accumulator with ``op``.

    ``ell`` must be an edge-POSITION structure (``ell_out`` / ``ell_dst``
    / ``ell_src``): slots index into the partition's (E,) edge arrays,
    so ``vals`` must be aligned with that edge order and already carry
    ``identity`` at inactive/padding edges.  Rows no edge touches come
    back as ``identity`` — callers pass the same sentinel the old
    scatter idiom initialized its accumulator with (0, INT_INF, ...).
    """
    _check_op(op)
    return _scatter_combine(g, ell, vals, op, identity, mode or get_mode())


# ---------------------------------------------------------------------------
# push_combine
# ---------------------------------------------------------------------------

@device_scope("localops.push_combine")
def push_combine(g: dict, ell_in: EllMeta, ell_dst: EllMeta, x, op: str, *,
                 identity, mode: str | None = None):
    """Combine a per-SOURCE vertex field over each destination's arcs.

    ``x`` is the partition's (n_local,) field, already masked: a source
    that sends nothing carries ``identity`` (``where(frontier, sigma,
    0)``).  Returns the (n,) accumulator ``acc[v] = op over arcs u->v of
    x[u]`` that ``scatter_combine(g, ell_dst, where(valid, x[srcl],
    identity), op)`` returns, by one of two routes, chosen from the
    partition count:

    * parts=1 (``ell_in`` has ``ell_dst``'s n rows): ``ell_in`` holds
      the same (destination, source) slots as ``ell_dst`` composed with
      ``out_src_local``, in the same order, so ``x`` is gathered once
      per ``ell_in`` slot (scopes ``ell_in.b<i>``);
    * parts>1, no ELL arrays, or the ref mode: ``x`` is gathered into
      out-edge order and combined through ``ell_dst`` (scopes
      ``ell_dst.b<i>``), a second gather per arc that an ``ell_in`` of
      local rows cannot save.
    """
    mode = mode or get_mode()
    _check_op(op)
    if (mode != "ref" and ell_in.n_rows == ell_dst.n_rows
            and _has_ell(g, ell_in)):
        return _combine_slots(g, ell_in, x, op, identity, mode)
    vals = jnp.where(g["out_dst_global"] < ell_dst.n_rows,
                     x[g["out_src_local"]], identity)
    return _scatter_combine(g, ell_dst, vals, op, identity, mode)
