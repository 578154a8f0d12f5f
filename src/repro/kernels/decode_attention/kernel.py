"""Single-token GQA decode attention over a KV cache — Pallas TPU kernel.

Flash-decoding adapted to TPU: grid (batch, kv_heads, kv_blocks) with the KV
axis innermost/sequential, carrying online-softmax stats in VMEM scratch. The
q block is the (rep = Hq/Hkv, D) group of query heads sharing one kv head —
small rows are fine on the VPU/MXU since D is 128-aligned. The valid cache
length (decode position + 1) arrives as a scalar-prefetch argument so one
compiled kernel serves every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr,
                   *, sm_scale: float, block_k: int, kv_blocks: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # (rep, D)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (rep, bk)
    cols = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(cols < len_ref[0], s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "sm_scale",
                                             "interpret"))
def decode_attention_grouped(q, k, v, kv_len, *, block_k: int = 512,
                             sm_scale: float | None = None,
                             interpret: bool = False):
    """q: (B, Hkv, rep, D); k, v: (B, Hkv, S, D); kv_len: () int32 (valid
    cache length). Returns (B, Hkv, rep, D)."""
    b, hkv, rep, d = q.shape
    s = k.shape[2]
    block_k = min(block_k, s)
    assert s % block_k == 0
    kv_blocks = s // block_k
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _decode_kernel, sm_scale=float(sm_scale), block_k=block_k,
        kv_blocks=kv_blocks)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, j, *_: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, j, *_: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, d), lambda b_, h, j, *_: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 128), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), q, k, v)
