"""PartitionedVector: the ``hpx::partitioned_vector`` analogue.

A global per-vertex array lives as (P, n_local) sharded over the "parts"
mesh axis.  HPX exposes remote element access through AGAS; the SPMD
analogue is bulk exchange, so this module provides the three exchange
primitives the graph algorithms are built from:

  * exchange_sum -- each partition holds a full-length (n,) accumulator
      of proposed updates; a single fused ``psum_scatter`` delivers the
      combined slice to each owner.  This is the TPU-native form of the
      paper's "remote contributions are sent and atomically applied at
      the owner" (message aggregation replaces fine-grained atomics).
  * exchange_or -- boolean OR-combine over a PACKED uint32 bitmap:
      n/32 words on the wire (the old bool->int32 inflation shipped 4n
      bytes, 32x more).
  * exchange_min_int -- owner-combining with MIN (parent selection in
      BFS replaces compare_exchange); implemented with all_to_all.
  * broadcast_global -- all-gather a (P, n_local) field into a full (n,)
      replica on every partition (pull-mode reads).

The bit-packing helpers (``pack_bits`` / ``unpack_bits`` / ``test_bit``)
live here too - they are exchange-payload machinery shared by the
packed OR exchange, the direction-optimizing BFS frontier bitmap, and
the frontier-pull kernels.

All exchange functions are meant to be called INSIDE shard_map over
axis "parts".

Every primitive routes its OUTGOING payload through ``_tap`` before
the collective — first the telemetry wire tap (``obs/telemetry.py``
byte accounting at trace time), then the deterministic chaos-injection
point (see ``core/faults.py``); both are Python-level no-ops unless
armed.  Ops: ``sum`` / ``min`` / ``or`` / ``bcast``; the blocking and
double-buffered forms share op names so one schedule (or one wire
report) addresses both execution modes.  ``psum_scalar`` is NOT
tapped: the BSP halt scalar is control plane, not payload — async
programs piggyback their halt count on the data exchange, where it IS
faultable (and counted).

Each primitive runs inside its device scope ``exchange.<op>``
(``obs/scopes.py``; ``psum_scalar`` is ``exchange.psum``), so a
profiler trace can tell exchange time from local work.  At parts=1
every exchange is the identity and its scope holds next to nothing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.obs import telemetry as obs_telemetry
from repro.obs.scopes import device_scope

AXIS = "parts"


def _tap(op: str, payload, axis_name: str):
    """Every exchange routes its outgoing payload through here: the
    telemetry wire tap first (trace-time byte accounting, a no-op
    unless ``obs.telemetry.recording`` is armed), then the chaos-
    injection tap (``faults.tap``, a no-op unless a schedule is armed).
    Both read the payload the collective actually ships, so the byte
    figure telemetry reports is the post-packing wire size."""
    obs_telemetry.tap_wire(op, payload)
    return faults.tap(op, payload, axis_name)


def pack_bits(bits):
    """(m,) bool -> (m/32,) uint32 (m must be a multiple of 32)."""
    m = bits.shape[0]
    w = bits.reshape(m // 32, 32).astype(jnp.uint32)
    return (w << jnp.arange(32, dtype=jnp.uint32)).sum(axis=1,
                                                       dtype=jnp.uint32)


def unpack_bits(packed, m):
    """(m/32,) uint32 -> (m,) bool."""
    idx = jnp.arange(m, dtype=jnp.int32)
    return ((packed[idx >> 5] >> (idx & 31).astype(jnp.uint32)) & 1
            ).astype(bool)


def test_bit(packed, idx):
    """Gather bit idx (any shape int32) from a packed bitmap."""
    word = packed[idx >> 5]
    return (word >> (idx & 31).astype(jnp.uint32)) & 1


def local_slice_bounds(n_local: int):
    """[lo, hi) global ids owned by this partition (inside shard_map)."""
    idx = jax.lax.axis_index(AXIS)
    lo = idx * n_local
    return lo, lo + n_local


@device_scope("exchange.sum")
def exchange_sum(acc_global, axis_name: str = AXIS):
    """acc_global: (n,) proposed updates for ALL vertices (local view).

    Returns (n_local,) combined updates for the vertices THIS partition
    owns.  One reduce-scatter on the wire: (P-1)/P * n elements.
    """
    parts = jax.lax.axis_size(axis_name)
    blocks = _tap("sum", acc_global.reshape(parts, -1), axis_name)
    return jax.lax.psum_scatter(blocks, axis_name, scatter_dimension=0,
                                tiled=False).reshape(-1)


@device_scope("exchange.or")
def exchange_or(mask_global, axis_name: str = AXIS):
    """Boolean OR-combine: frontiers/activation masks.

    The mask is bit-PACKED before it touches the wire: each partition
    ships its (n/32,) uint32 bitmap through one all_to_all and owners
    OR the P candidate rows - n/8 bytes total per partition instead of
    the 4n an int32-inflated psum_scatter pays (32x less wire).
    """
    parts = jax.lax.axis_size(axis_name)
    n_local_words = mask_global.shape[0] // parts // 32
    packed = _tap(
        "or", pack_bits(mask_global).reshape(parts, n_local_words),
        axis_name)
    rows = jax.lax.all_to_all(
        packed.reshape(parts, 1, n_local_words), axis_name,
        split_axis=0, concat_axis=1)                    # (1, P, nl/32)
    acc = jax.lax.reduce(rows[0], jnp.uint32(0), jax.lax.bitwise_or, (0,))
    return unpack_bits(acc, mask_global.shape[0] // parts)


@device_scope("exchange.min")
def exchange_min_int(val_global, axis_name: str = AXIS, big=None):
    """Element-wise MIN combine of proposals (any ordered dtype —
    int32 parents/labels, f32 distances).

    all_to_all moves each partition's (P, n_local) proposal matrix so
    that owners receive P candidate rows; min over the row axis.
    """
    parts = jax.lax.axis_size(axis_name)
    blocks = _tap("min", val_global.reshape(parts, -1), axis_name)
    rows = jax.lax.all_to_all(blocks.reshape(parts, 1, -1), axis_name,
                              split_axis=0,
                              concat_axis=1)          # (1, P, n_local)
    return rows.min(axis=(0, 1))


@device_scope("exchange.bcast")
def broadcast_global(local_vals, axis_name: str = AXIS):
    """(n_local,) -> (n,) full replica (all-gather)."""
    return jax.lax.all_gather(_tap("bcast", local_vals, axis_name),
                              axis_name, axis=0, tiled=True)


@device_scope("exchange.psum")
def psum_scalar(x, axis_name: str = AXIS):
    return jax.lax.psum(x, axis_name)


# --------------------------------------------------------------------------
# Double-buffered exchange: start / finish pairs.
#
# The blocking primitives above fuse "ship the proposals" and "combine at
# the owner" into one call, which is exactly the BSP barrier the source
# paper blames for latency-bound scaling.  The ``*_start`` forms below
# issue ONLY the wire movement (all_to_all / psum_scatter) and return the
# raw received rows as an opaque in-flight handle — a plain array pytree
# that an async driver carries across a ``lax.while_loop`` iteration.  The
# matching ``*_finish`` forms are pure local reductions over the handle.
# Round k's handle is finished AFTER round k+1's local compute, so the
# local work overlaps the in-flight collective (the serve executor's
# device/host overlap, replayed inside the superstep loop).
#
# Every start form also piggybacks one reduction scalar (a halt count or
# residual) as an extra payload column, so convergence detection rides
# the data exchange instead of paying a separate psum collective per
# round.  Each partition stamps its local scalar on all P outgoing rows;
# after the exchange the receiver holds all P stamps, and summing them
# reproduces ``psum_scalar`` bit-for-bit (integer-valued scalars stay
# exact in f32 payloads up to 2**24; the property suite pins this).
# --------------------------------------------------------------------------


@device_scope("exchange.min_start")
def exchange_min_start(val_global, scalar, axis_name: str = AXIS):
    """Issue the MIN-combine exchange of ``(n,)`` proposals without
    reducing.  ``scalar`` (the piggybacked halt count) is appended as a
    trailing payload column in the proposal dtype.  Returns the in-flight
    handle: ``(1, P, n_local + 1)`` received rows."""
    parts = jax.lax.axis_size(axis_name)
    n_local = val_global.shape[0] // parts
    blocks = val_global.reshape(parts, n_local)
    payload = _tap("min", jnp.concatenate(
        [blocks, jnp.full((parts, 1), scalar, blocks.dtype)], axis=1),
        axis_name)
    return jax.lax.all_to_all(payload.reshape(parts, 1, n_local + 1),
                              axis_name, split_axis=0, concat_axis=1)


@device_scope("exchange.min_finish")
def exchange_min_finish(handle):
    """Pure-local reduction of an :func:`exchange_min_start` handle:
    ``((n_local,) combined minima, global scalar sum)``."""
    rows = handle[0]                            # (P, n_local + 1)
    return rows[:, :-1].min(axis=0), rows[:, -1].sum()


@device_scope("exchange.sum_start")
def exchange_sum_start(acc_global, scalar, axis_name: str = AXIS):
    """Issue the SUM-combine reduce-scatter of ``(n,)`` proposals with a
    piggybacked scalar column.  ``psum_scatter`` combines on the wire, so
    the handle is already reduced data — the split still buys the driver
    a full local-compute window before :func:`exchange_sum_finish` reads
    it.  Returns the ``(n_local + 1,)`` handle."""
    parts = jax.lax.axis_size(axis_name)
    n_local = acc_global.shape[0] // parts
    blocks = acc_global.reshape(parts, n_local)
    payload = _tap("sum", jnp.concatenate(
        [blocks, jnp.full((parts, 1), scalar, blocks.dtype)], axis=1),
        axis_name)
    return jax.lax.psum_scatter(payload, axis_name, scatter_dimension=0,
                                tiled=False)


@device_scope("exchange.sum_finish")
def exchange_sum_finish(handle):
    """``((n_local,) combined sums, global scalar sum)``."""
    return handle[:-1], handle[-1]


@device_scope("exchange.or_start")
def exchange_or_start(mask_global, scalar, axis_name: str = AXIS):
    """Issue the bit-packed OR exchange of an ``(n,)`` bool mask with a
    piggybacked uint32 count word.  Returns the ``(1, P, n_words + 1)``
    handle; finish with :func:`exchange_or_finish` (which needs the
    static ``n_local`` because the handle itself stays a pure array
    pytree a loop carry can hold)."""
    parts = jax.lax.axis_size(axis_name)
    n_local_words = mask_global.shape[0] // parts // 32
    blocks = pack_bits(mask_global).reshape(parts, n_local_words)
    payload = _tap("or", jnp.concatenate(
        [blocks, jnp.full((parts, 1), scalar, jnp.uint32)], axis=1),
        axis_name)
    return jax.lax.all_to_all(payload.reshape(parts, 1, n_local_words + 1),
                              axis_name, split_axis=0, concat_axis=1)


@device_scope("exchange.or_finish")
def exchange_or_finish(handle, n_local: int):
    """``((n_local,) bool OR-combined mask, global int32 scalar sum)``."""
    rows = handle[0]                            # (P, n_words + 1)
    acc = jax.lax.reduce(rows[:, :-1], jnp.uint32(0),
                         jax.lax.bitwise_or, (0,))
    return unpack_bits(acc, n_local), rows[:, -1].sum().astype(jnp.int32)
