"""BFS pull-step Pallas kernel.

For a tile of (unvisited) local vertices with ELL-packed in-neighbor
lists, test each neighbor against the packed global frontier bitmap and
emit (hit, min-parent) per vertex - the owner-side parent derivation of
the HPX-adapted BFS (core/bfs.py).

Per grid step the kernel sees:
  nbr_ref  (RB, K) int32 global neighbor ids (sentinel = n_pad)
  bits_ref (n_words,) uint32 packed frontier (resident in VMEM: n/32)
  unv_ref  (RB,) int32 1 = unvisited
and writes parent_ref (RB,) int32 (INT_INF when no frontier neighbor).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


INT_INF = 2 ** 30


def _frontier_kernel(nbr_ref, bits_ref, unv_ref, parent_ref):
    nbr = nbr_ref[...]                               # (RB, K)
    bits = bits_ref[...]                             # (W,)
    unv = unv_ref[...]                               # (RB,)
    word = jnp.take(bits, nbr >> 5, axis=0)          # (RB, K) u32
    hit = ((word >> (nbr & 31).astype(jnp.uint32)) & 1) == 1
    cand = jnp.where(hit, nbr, jnp.int32(INT_INF))
    parent = cand.min(axis=1)                        # min-id parent
    parent_ref[...] = jnp.where(unv == 1, parent, jnp.int32(INT_INF))


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def bfs_pull(nbr, bits, unvisited, *, row_block: int = 256,
             interpret: bool = False):
    """nbr: (n_rows, K) int32 < 32*len(bits); bits: (W,) uint32;
    unvisited: (n_rows,) int32. Returns parents (n_rows,) int32."""
    n_rows, k = nbr.shape
    assert n_rows % row_block == 0, (n_rows, row_block)
    grid = (n_rows // row_block,)
    return pl.pallas_call(
        _frontier_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block, k), lambda r: (r, 0)),
            pl.BlockSpec(bits.shape, lambda r: (0,)),
            pl.BlockSpec((row_block,), lambda r: (r,)),
        ],
        out_specs=pl.BlockSpec((row_block,), lambda r: (r,)),
        out_shape=jax.ShapeDtypeStruct((n_rows,), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(nbr, bits, unvisited.astype(jnp.int32))
