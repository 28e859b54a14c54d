"""Blocked online-softmax (flash) attention Pallas kernel.

Forward-only TPU kernel used for LM training/prefill compute; the
backward pass uses the custom-VJP XLA path (models/layers.py), whose
blocked recompute is already memory-optimal - the kernel accelerates the
forward hot loop on the MXU.

Grid: (B*H, n_q_blocks, n_kv_blocks), kv innermost with "arbitrary"
semantics so the VMEM scratch accumulators (m, l, acc) persist across kv
steps; the output block is written on the last kv step.  BlockSpecs keep
one (Bq, D) q tile and one (Bk, D) k/v tile in VMEM per step; D and the
block sizes should be multiples of 128 for MXU alignment (danube3's
head_dim 120 is padded by ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  causal: bool, window: int, bq: int, bk: int, nk: int,
                  softcap: float):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    q = q_ref[0]                                  # (Bq, D)
    k = k_ref[0]                                  # (Bk, D)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ()))) * (q.shape[-1] ** -0.5)   # (Bq, Bk)
    if softcap and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    qp = q_i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kp = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= kp > qp - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]
    l_prev = l_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(p.astype(v.dtype), v,
                             (((1,), (0,)), ((), ()))).astype(jnp.float32)
    acc_sc[...] = acc_sc[...] * corr[:, None] + pv
    m_sc[...] = m_new
    l_sc[...] = l_new

    @pl.when(kv_i == nk - 1)
    def _finish():
        o_ref[0] = (acc_sc[...]
                    / jnp.maximum(l_sc[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 256,
                        block_k: int = 256, interpret: bool = False):
    """q/k/v: (BH, S, D) flattened batch*heads. Returns (BH, S, D)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    nq, nk = sq // bq, sk // bk
    grid = (bh, nq, nk)
    kern = functools.partial(_flash_kernel, causal=causal, window=window,
                             bq=bq, bk=bk, nk=nk, softcap=softcap)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
