"""ELL SpMV Pallas kernel: y[r] = sum_k val[r,k] * x[idx[r,k]].

TPU adaptation of the paper's PageRank contribution accumulation (the
per-partition SpMV between exchanges).  The GPU-style CSR row-per-thread
formulation does not map to the TPU's vector units; instead rows are
ELL-packed (fixed K slots, sentinel-padded) so a (RB, K) tile is a dense
VPU-friendly block, and the x vector is resident in VMEM (per-partition
slices are O(n/P) = a few MB at production scale).

BlockSpec tiling: grid over row blocks; per step the kernel sees
  idx_ref (RB, K) int32 | val_ref (RB, K) f32 | x_ref (n_pad,) f32
and writes y_ref (RB,).  Gathers from VMEM use vectorized jnp.take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _spmv_kernel(idx_ref, val_ref, x_ref, y_ref):
    idx = idx_ref[...]                        # (RB, K) int32, sentinel = n_pad-1
    val = val_ref[...]                        # (RB, K) f32 (0.0 at padding)
    x = x_ref[...]                            # (n_pad,) f32
    gathered = jnp.take(x, idx, axis=0)       # VMEM gather
    y_ref[...] = (gathered * val).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def spmv_ell(idx, val, x, *, row_block: int = 256, interpret: bool = False):
    """idx/val: (n_rows, K); x: (n_cols,). Returns y: (n_rows,) f32.

    n_rows must be a multiple of row_block; padding entries must carry
    val == 0 (idx may point anywhere valid).
    """
    n_rows, k = idx.shape
    assert n_rows % row_block == 0, (n_rows, row_block)
    grid = (n_rows // row_block,)
    return pl.pallas_call(
        _spmv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block, k), lambda r: (r, 0)),
            pl.BlockSpec((row_block, k), lambda r: (r, 0)),
            pl.BlockSpec(x.shape, lambda r: (0,)),   # x resident in VMEM
        ],
        out_specs=pl.BlockSpec((row_block,), lambda r: (r,)),
        out_shape=jax.ShapeDtypeStruct((n_rows,), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(idx, val, x.astype(jnp.float32))
