"""PS-DSF per-server VDS reduction — Pallas TPU kernel.

The hot loop of a datacenter-scale scheduler tick (Section III-D runs on
every server every T seconds): given global task counts x_n, weights phi_n
and the gamma matrix, compute for every server i
    S*_i     = min_n  x_n / (phi_n * gamma[n, i])     (Eq. 16)
    argmin_i = the user attaining it
over N ~ 10^4..10^6 users. Grid (server_tiles, user_tiles) with the user
axis innermost/sequential, carrying running (min, argmin) per server column
in VMEM scratch. Ineligible pairs (gamma == 0) are +inf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BIG = 3.0e38


def _vds_kernel(xphi_ref, gamma_ref, min_ref, arg_ref,
                min_scr, arg_scr, *, block_n: int, n_tiles: int):
    nj = pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        min_scr[...] = jnp.full_like(min_scr, BIG)
        arg_scr[...] = jnp.zeros_like(arg_scr)

    xphi = xphi_ref[...]                                   # (bn, 1) f32
    gamma = gamma_ref[...]                                 # (bn, bk)
    snorm = jnp.where(gamma > 0, xphi / jnp.where(gamma > 0, gamma, 1.0), BIG)
    rows = nj * block_n + jax.lax.broadcasted_iota(
        jnp.int32, snorm.shape, 0)
    tile_min = jnp.min(snorm, axis=0, keepdims=True)       # (1, bk)
    tile_arg = jnp.min(jnp.where(snorm <= tile_min, rows, jnp.int32(2**31 - 1)),
                       axis=0, keepdims=True)
    better = tile_min < min_scr[...]
    arg_scr[...] = jnp.where(better, tile_arg, arg_scr[...])
    min_scr[...] = jnp.where(better, tile_min, min_scr[...])

    @pl.when(nj == n_tiles - 1)
    def _finish():
        min_ref[...] = min_scr[...]
        arg_ref[...] = arg_scr[...]


@functools.partial(jax.jit, static_argnames=("block_n", "block_k",
                                             "interpret"))
def vds_argmin(x_over_phi, gamma, *, block_n: int = 256, block_k: int = 128,
               interpret: bool = False):
    """x_over_phi: (N,) f32 (= x_n / phi_n); gamma: (N, K).
    Returns (min_vds (K,), argmin_user (K,) int32)."""
    n, k = gamma.shape
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert n % block_n == 0 and k % block_k == 0, (n, k, block_n, block_k)
    n_tiles = n // block_n
    k_tiles = k // block_k

    kernel = functools.partial(_vds_kernel, block_n=block_n, n_tiles=n_tiles)
    min_out, arg_out = pl.pallas_call(
        kernel,
        grid=(k_tiles, n_tiles),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda ki, nj: (nj, 0)),
            pl.BlockSpec((block_n, block_k), lambda ki, nj: (nj, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k), lambda ki, nj: (0, ki)),
            pl.BlockSpec((1, block_k), lambda ki, nj: (0, ki)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_k), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_over_phi.astype(jnp.float32)[:, None], gamma)
    return min_out[0], arg_out[0]
