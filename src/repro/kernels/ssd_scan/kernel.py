"""Mamba-2 SSD chunked scan — Pallas TPU kernel (ngroups = 1).

Grid (batch, heads, chunks); chunks innermost/sequential, carrying the
(P, N) recurrent state in VMEM scratch across chunk steps. Each step does
three MXU matmuls (C B^T scores, intra-chunk y, state update) over one
(Q, P)/(Q, N) chunk — the TPU-native replacement for Mamba-1's sequential
selective scan (see DESIGN.md hardware-adaptation notes).

Per-head decay rate A[h] arrives as a scalar-prefetch argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref,
                state_scr, *, chunk: int):
    h = pl.program_id(1)
    cj = pl.program_id(2)

    @pl.when(cj == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = a_ref[h]                                              # scalar (<= 0)
    x = x_ref[0, 0].astype(jnp.float32)                       # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                     # (Q, 128) bcast
    dt1 = dt[:, :1]                                           # (Q, 1)
    bm = b_ref[0].astype(jnp.float32)                         # (Q, N)
    cm = c_ref[0].astype(jnp.float32)                         # (Q, N)

    dta = dt1 * a                                             # (Q, 1)
    seg = jnp.cumsum(dta, axis=0)                             # (Q, 1)
    # intra-chunk: y_diag[i] = sum_{j<=i} (C_i.B_j) exp(seg_i-seg_j) dt_j x_j
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    decay = jnp.exp(seg - seg.T)                              # (Q, Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    w = jnp.where(rows >= cols, scores * decay, 0.0) * dt1.T  # (Q, Q)
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_off[i] = exp(seg_i) * C_i . state^T
    state = state_scr[...]                                    # (P, N)
    y_off = jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + y_off * jnp.exp(seg)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: state' = state * exp(sum dta) + sum_j w_j x_j b_j^T
    last = seg[chunk - 1:chunk, :]                            # (1, 1)
    wstate = jnp.exp(last - seg) * dt1                        # (Q, 1)
    zc = jax.lax.dot_general(x, bm * wstate, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state * jnp.exp(last) + zc


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk: int = 128,
             interpret: bool = False):
    """x: (B, H, S, P); dt: (B, H, S) post-softplus; a: (H,) negative;
    b_mat, c_mat: (B, S, N). Returns y (B, H, S, P)."""
    bsz, h, s, p_dim = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    # broadcast dt to a lane-friendly (B, H, S, 128) layout
    dt4 = jnp.broadcast_to(dt[..., None], dt.shape + (128,))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p_dim), lambda b_, h_, c, *_: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, 128), lambda b_, h_, c, *_: (b_, h_, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c, *_: (b_, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c, *_: (b_, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p_dim),
                               lambda b_, h_, c, *_: (b_, h_, c, 0)),
        scratch_shapes=[pltpu.VMEM((p_dim, n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(a, jnp.float32), x, dt4, b_mat, c_mat)
