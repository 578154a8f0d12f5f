"""PS-DSF whole-cluster bisection fill — Pallas TPU kernel.

One saturation *event* of the sort-free fill engine (``fill="bisect"``,
see ``core/placement.server_fill_rdm_bisect``) for every server at once:
given per-(user, server) floors and active rates, per-user demands and
per-server capacities (plus the frozen usage / saturated masks carried by
the event loop), find each server's first crossing level of the monotone
piecewise-linear usage

    U_{i,r}(L) = frozen_{i,r} + sum_n d_{n,r} rate_{n,i} max(0, L - f_{n,i})

by bisection, entirely on-chip. Grid is (server_tiles, phases, user_tiles)
with the user axis innermost/sequential: phase 0 accumulates the total
slope and max active floor, phase 1 the usage at the bracket base (to set
the upper bracket via the tightest headroom/slope step), phases
2..steps+1 are the bisection iterations — the (lo, hi) bracket lives in
VMEM scratch and each iteration is one tiled pass of
(users x servers) * (users x resources) contractions — and the final
phase emits the level plus the usage/local-slope/total-slope the event
loop needs for its bind test. The outer event loop (<= R+1 iterations of
freeze-and-repeat) stays in jnp in ``ops.fill_cluster_padded``.

Dtype-generic: blocks and scratch take the input dtype, so interpret mode
under ``jax.config.enable_x64`` reproduces the f64 engines to ~1e-13
(parity-gated in tests); on-TPU use is f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BIG = 3.0e38
TOL = 1e-9


def _fill_kernel(floors_ref, rate_ref, dem_ref, caps_ref, frz_ref, sat_ref,
                 lvl_ref, lvl_out, u_out, lsl_out, slope_out,
                 slope_s, fmax_s, lo_s, hi_s, acc_s, acc2_s,
                 *, steps: int, n_tiles: int):
    s = pl.program_id(1)
    nj = pl.program_id(2)
    floors = floors_ref[...]                               # (bn, bk)
    rate = rate_ref[...]                                   # (bn, bk)
    dem = dem_ref[...]                                     # (bn, R)
    last = nj == n_tiles - 1

    @pl.when((s == 0) & (nj == 0))
    def _init():
        slope_s[...] = jnp.zeros_like(slope_s)
        fmax_s[...] = jnp.zeros_like(fmax_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        acc2_s[...] = jnp.zeros_like(acc2_s)
        lo_s[...] = lvl_ref[...]
        hi_s[...] = jnp.zeros_like(hi_s)

    @pl.when(s == 0)
    def _slope_pass():
        slope_s[...] += jnp.dot(rate.T, dem)
        fmax_s[...] = jnp.maximum(
            fmax_s[...],
            jnp.max(jnp.where(rate > 0, floors, 0.0), axis=0, keepdims=True))

        @pl.when(last)
        def _():
            hi_s[...] = jnp.maximum(fmax_s[...], lo_s[...])

    @pl.when(s == 1)
    def _bracket_pass():
        hi0 = hi_s[...]                                    # (1, bk)
        acc_s[...] += jnp.dot((rate * jnp.maximum(hi0 - floors, 0.0)).T, dem)

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
            hi_s[...] = jnp.where(has[None, :], hi0 + step_up[None, :],
                                  lo_s[...])
            acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when((s >= 2) & (s < 2 + steps))
    def _bisect_pass():
        mid = 0.5 * (lo_s[...] + hi_s[...])                # (1, bk)
        acc_s[...] += jnp.dot((rate * jnp.maximum(mid - floors, 0.0)).T, dem)

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
        acc_s[...] += jnp.dot((rate * jnp.maximum(lvl - floors, 0.0)).T, dem)
        acc2_s[...] += jnp.dot((rate * (floors <= lvl)).T, dem)

        @pl.when(last)
        def _():
            lvl_out[...] = lvl
            u_out[...] = frz_ref[...] + acc_s[...]
            lsl_out[...] = acc2_s[...]
            slope_out[...] = slope_s[...]


@functools.partial(jax.jit, static_argnames=("steps", "block_n", "block_k",
                                             "interpret"))
def fill_event_levels(floors, rate, demands, caps, frozen, saturated, level,
                      *, steps: int = 48, block_n: int = 256,
                      block_k: int = 128, interpret: bool = False):
    """One bisection saturation event for every server.

    floors/rate: (N, K) active-masked (rate == 0 for frozen/ineligible
    users, their floors 0); demands: (N, R); caps/frozen: (K, R);
    saturated: (K, R) 0/1 mask in the compute dtype; level: (K,) current
    per-server fill level. Returns (level' (K,), usage (K, R),
    local_slope (K, R), total_slope (K, R)) at the event level — exactly
    what the event loop's bind test consumes. Shapes must already be
    multiples of the block sizes (``ops.fill_cluster_padded`` pads).
    """
    n, k = floors.shape
    r = demands.shape[1]
    dt = floors.dtype
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert n % block_n == 0 and k % block_k == 0, (n, k, block_n, block_k)
    n_tiles = n // block_n
    k_tiles = k // block_k

    kernel = functools.partial(_fill_kernel, steps=steps, n_tiles=n_tiles)
    lvl, u, lsl, slope = pl.pallas_call(
        kernel,
        grid=(k_tiles, steps + 3, n_tiles),
        in_specs=[
            pl.BlockSpec((block_n, block_k), lambda ki, s, nj: (nj, ki)),
            pl.BlockSpec((block_n, block_k), lambda ki, s, nj: (nj, ki)),
            pl.BlockSpec((block_n, r), lambda ki, s, nj: (nj, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, nj: (ki, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, nj: (ki, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, nj: (ki, 0)),
            pl.BlockSpec((1, block_k), lambda ki, s, nj: (0, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k), lambda ki, s, nj: (0, ki)),
            pl.BlockSpec((block_k, r), lambda ki, s, nj: (ki, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, nj: (ki, 0)),
            pl.BlockSpec((block_k, r), lambda ki, s, nj: (ki, 0)),
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
    )(floors, rate, demands, caps, frozen, saturated, level[None, :])
    return lvl[0], u, lsl, slope
