"""Causal GQA flash attention — Pallas TPU kernel.

Layout (B, H, S, D). Grid (batch, q_heads, q_blocks, kv_blocks); the kv axis
is the innermost, sequentially-iterated dimension, carrying the online-softmax
running statistics in VMEM scratch across kv steps (the canonical Pallas-TPU
flash structure). GQA maps q-head h to kv-head h // (Hq // Hkv) in the K/V
BlockSpec index maps.

VMEM working set per grid step: q (bq, D) + k/v (bk, D) + acc (bq, D) f32 +
stats (bq, 128) f32 — e.g. bq = bk = 512, D = 128: ~1.4 MB, comfortably
inside the ~16 MB v5e VMEM; MXU dims (bq x D x bk) are 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr,
                  *, sm_scale: float, block_q: int, block_k: int,
                  causal: bool, kv_blocks: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale         # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

    m_prev = m_scr[:, :1]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                 # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                         # (bq, 1)
    l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                    # (bk, D)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "sm_scale", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         block_q: int = 512, block_k: int = 512,
                         sm_scale: float | None = None,
                         interpret: bool = False):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D) -> (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    q_blocks, kv_blocks = s // block_q, s // block_k
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _flash_kernel, sm_scale=float(sm_scale), block_q=block_q,
        block_k=block_k, causal=causal, kv_blocks=kv_blocks)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, rep=rep: (b_, h // rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, rep=rep: (b_, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
