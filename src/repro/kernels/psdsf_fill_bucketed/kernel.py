"""Bucketed PS-DSF bisection fill — Pallas TPU kernel.

The sparse-eligibility twin of ``kernels/psdsf_fill``: instead of
contracting full (N, K) floor/rate matrices against (N, R) demands, every
server works on its pre-gathered eligibility *bucket* (``core.layout``) —
(K, Bmax) floors/rates plus a (K, Bmax, R) gathered-demand tensor — so one
saturation event costs O(K * Bmax * R) instead of O(N * K * R). Padded
bucket slots carry rate 0, making them exactly inert.

Per server i the monotone piecewise-linear usage is

    U_{i,r}(L) = frozen_{i,r}
                 + sum_b dem_b[i,b,r] rate_b[i,b] max(0, L - floors_b[i,b])

and the kernel finds each server's first capacity crossing by bisection.
Grid is (server_tiles, phases, bucket_tiles) with the bucket axis
innermost/sequential — the same phase schedule as the dense kernel
(0: total slope + max active floor, 1: upper bracket from the tightest
headroom/slope step, 2..steps+1: bisection with the (lo, hi) bracket in
VMEM scratch, final: emit level/usage/local-slope/total-slope for the
event loop's bind test in ``ops.fill_cluster_bucketed_padded``). The
per-server contractions are batched elementwise-multiply-reduce over the
bucket axis (VPU, no MXU needed), which is what makes the bucket layout
free to exploit here.

Dtype-generic like the dense kernel: f64 under ``jax.config.enable_x64``
(interpret parity ~1e-13, gated in tests), f32 on-TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BIG = 3.0e38
TOL = 1e-9


def _fill_bucketed_kernel(floors_ref, rate_ref, dem_ref, caps_ref, frz_ref,
                          sat_ref, lvl_ref, lvl_out, u_out, lsl_out,
                          slope_out, slope_s, fmax_s, lo_s, hi_s, acc_s,
                          acc2_s, *, steps: int, b_tiles: int):
    s = pl.program_id(1)
    bj = pl.program_id(2)
    floors = floors_ref[...]                               # (bk, bb)
    rate = rate_ref[...]                                   # (bk, bb)
    dem = dem_ref[...]                                     # (bk, bb, R)
    last = bj == b_tiles - 1

    def contract(w):
        # per-server bucket contraction: (bk, bb) weights x (bk, bb, R)
        # demands -> (bk, R) usage contribution
        return (w[:, :, None] * dem).sum(axis=1)

    @pl.when((s == 0) & (bj == 0))
    def _init():
        slope_s[...] = jnp.zeros_like(slope_s)
        fmax_s[...] = jnp.zeros_like(fmax_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        acc2_s[...] = jnp.zeros_like(acc2_s)
        lo_s[...] = lvl_ref[...]
        hi_s[...] = jnp.zeros_like(hi_s)

    @pl.when(s == 0)
    def _slope_pass():
        slope_s[...] += contract(rate)
        fmax_s[...] = jnp.maximum(
            fmax_s[...],
            jnp.max(jnp.where(rate > 0, floors, 0.0), axis=1)[None, :])

        @pl.when(last)
        def _():
            hi_s[...] = jnp.maximum(fmax_s[...], lo_s[...])

    @pl.when(s == 1)
    def _bracket_pass():
        hi0 = hi_s[...].T                                  # (bk, 1)
        acc_s[...] += contract(rate * jnp.maximum(hi0 - floors, 0.0))

        @pl.when(last)
        def _():
            cap = caps_ref[...]                            # (bk, R)
            slope = slope_s[...]
            canb = (sat_ref[...] == 0) & (slope > TOL)
            head = jnp.maximum(cap - frz_ref[...] - acc_s[...], 0.0)
            step_up = jnp.where(canb, head / jnp.maximum(slope, TOL),
                                BIG).min(axis=1)           # (bk,)
            has = canb.any(axis=1)
            # no resource can bind -> collapse the bracket so the level
            # (and hence the fill) is a no-op for that server
            hi_s[...] = jnp.where(has[None, :],
                                  hi_s[...] + step_up[None, :], lo_s[...])
            acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when((s >= 2) & (s < 2 + steps))
    def _bisect_pass():
        mid = 0.5 * (lo_s[...] + hi_s[...]).T              # (bk, 1)
        acc_s[...] += contract(rate * jnp.maximum(mid - floors, 0.0))

        @pl.when(last)
        def _():
            canb = (sat_ref[...] == 0) & (slope_s[...] > TOL)
            crossed = (canb & (frz_ref[...] + acc_s[...] >= caps_ref[...])
                       ).any(axis=1)[None, :]              # (1, bk)
            mid_b = 0.5 * (lo_s[...] + hi_s[...])
            lo_s[...] = jnp.where(crossed, lo_s[...], mid_b)
            hi_s[...] = jnp.where(crossed, mid_b, hi_s[...])
            acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(s == 2 + steps)
    def _output_pass():
        lvl = jnp.maximum(hi_s[...], lvl_ref[...])         # (1, bk)
        acc_s[...] += contract(rate * jnp.maximum(lvl.T - floors, 0.0))
        acc2_s[...] += contract(rate * (floors <= lvl.T))

        @pl.when(last)
        def _():
            lvl_out[...] = lvl
            u_out[...] = frz_ref[...] + acc_s[...]
            lsl_out[...] = acc2_s[...]
            slope_out[...] = slope_s[...]


@functools.partial(jax.jit, static_argnames=("steps", "block_b", "block_k",
                                             "interpret"))
def fill_event_levels_bucketed(floors, rate, dem_b, caps, frozen, saturated,
                               level, *, steps: int = 48, block_b: int = 256,
                               block_k: int = 128, interpret: bool = False):
    """One bisection saturation event for every server, bucket layout.

    floors/rate: (K, Bmax) active-masked per-bucket-slot (rate == 0 for
    frozen/ineligible/padded slots, their floors 0); dem_b: (K, Bmax, R)
    gathered demand rows; caps/frozen: (K, R); saturated: (K, R) 0/1 mask
    in the compute dtype; level: (K,) current per-server fill level.
    Returns (level' (K,), usage (K, R), local_slope (K, R), total_slope
    (K, R)) at the event level — same contract as the dense
    ``psdsf_fill.fill_event_levels``. Shapes must already be multiples of
    the block sizes (``ops.fill_cluster_bucketed_padded`` pads).
    """
    k, bmax = floors.shape
    r = dem_b.shape[2]
    dt = floors.dtype
    block_b = min(block_b, bmax)
    block_k = min(block_k, k)
    assert k % block_k == 0 and bmax % block_b == 0, (k, bmax, block_k,
                                                      block_b)
    b_tiles = bmax // block_b
    k_tiles = k // block_k

    kernel = functools.partial(_fill_bucketed_kernel, steps=steps,
                               b_tiles=b_tiles)
    lvl, u, lsl, slope = pl.pallas_call(
        kernel,
        grid=(k_tiles, steps + 3, b_tiles),
        in_specs=[
            pl.BlockSpec((block_k, block_b), lambda ki, s, bj: (ki, bj)),
            pl.BlockSpec((block_k, block_b), lambda ki, s, bj: (ki, bj)),
            pl.BlockSpec((block_k, block_b, r),
                         lambda ki, s, bj: (ki, bj, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, bj: (ki, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, bj: (ki, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, bj: (ki, 0)),
            pl.BlockSpec((1, block_k), lambda ki, s, bj: (0, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k), lambda ki, s, bj: (0, ki)),
            pl.BlockSpec((block_k, r), lambda ki, s, bj: (ki, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, bj: (ki, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, bj: (ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), dt),
            jax.ShapeDtypeStruct((k, r), dt),
            jax.ShapeDtypeStruct((k, r), dt),
            jax.ShapeDtypeStruct((k, r), dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, r), dt),
            pltpu.VMEM((1, block_k), dt),
            pltpu.VMEM((1, block_k), dt),
            pltpu.VMEM((1, block_k), dt),
            pltpu.VMEM((block_k, r), dt),
            pltpu.VMEM((block_k, r), dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(floors, rate, dem_b, caps, frozen, saturated, level[None, :])
    return lvl[0], u, lsl, slope
