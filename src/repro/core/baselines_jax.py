"""Jitted, vectorized twin of the exact baseline fillers (``baselines.py``).

A baseline (C-DRFH / TSF / CDRF) is a weighted max-min level fill whose level
rate is a server-independent score weight ``w_n`` on eligible servers — the
same per-server saturation-event fill and Gauss-Seidel sweep as PS-DSF with
``gamma[n, i]`` replaced by the (N, K) *level-rate matrix*. The solver body
is therefore shared verbatim with the PS-DSF engine (``psdsf_jax._solve_core``
in RDM mode); this module contributes the jnp level-rate construction plus
jitted single / vmapped-batched entry points mirroring ``psdsf_solve_jax`` /
``psdsf_solve_batched``, so baselines participate in batched scenario sweeps
at the same 10^3-user scales.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .baselines import LEVEL_FILL_MECHANISMS, level_rate_matrix
from .placement import ROUTED_FILL_CORRECTORS, SolveInfo, stranded_fraction
from .psdsf_jax import (_BIG, _batch_buckets, _check_accel, _check_placement,
                        _layout_arrays, _solve_core, _solve_dtype,
                        gamma_matrix_jnp)
from .types import Allocation, AllocationProblem

_TOL = 1e-9


def level_rate_matrix_jnp(demands, capacities, eligibility, mechanism: str):
    """jnp twin of ``baselines.level_rate_matrix`` (for jitted pipelines).

    Shapes: demands (N, R), capacities (K, R), eligibility (N, K).
    """
    g = gamma_matrix_jnp(demands, capacities, eligibility)
    if mechanism == "cdrfh":
        pooled = capacities.sum(axis=0)
        frac = jnp.where(demands > 0,
                         jnp.where(pooled[None, :] > 0,
                                   demands / jnp.maximum(pooled[None, :],
                                                         1e-300), _BIG),
                         0.0)
        maxd = frac.max(axis=1)
        w = jnp.where(maxd > 0, 1.0 / jnp.maximum(maxd, 1e-300), 0.0)
    elif mechanism == "tsf":
        g_unc = gamma_matrix_jnp(demands, capacities,
                                 jnp.ones_like(eligibility))
        w = g_unc.sum(axis=1)
    elif mechanism == "cdrf":
        w = g.sum(axis=1)
    else:
        raise ValueError(f"unknown level-fill mechanism {mechanism!r}; "
                         f"expected one of {LEVEL_FILL_MECHANISMS}")
    return jnp.where(g > 0, w[:, None], 0.0)


def _gamma_scale(demands, capacities, level_gamma):
    """Per-server monopolization scale for the acceptance band: the level
    rates sum gamma over servers, so scaling the residual tolerance by
    ``level_gamma.max()`` would loosen it ~linearly with K."""
    g = gamma_matrix_jnp(demands, capacities,
                         (level_gamma > 0).astype(demands.dtype))
    return g.max()


# ---------------------------------------------------------------------------
# Routed global fill: the jitted mirror of ``placement.routed_level_fill``
# ---------------------------------------------------------------------------

def _routed_fill_core(demands, capacities, weights, level_gamma,
                      correctors=ROUTED_FILL_CORRECTORS):
    """Headroom placement for the global-share mechanisms, traced: all
    users' levels rise together, each user's rate split across its eligible
    servers proportional to per-server headroom for its demand mix, splits
    re-derived at every saturation event (+ ``correctors`` midpoint
    passes). Same event structure as the numpy fill — a ``while_loop``
    bounded by K*R + N events, each saturating a (server, resource) pair or
    freezing a user. Returns (x, events, residual=0) matching the
    ``_solve_core`` output contract (the fill is one-shot exact: nothing
    iterates, nothing can fail to converge)."""
    n, r_cnt = demands.shape
    k = capacities.shape[0]
    dtype = _solve_dtype(demands)
    cap = capacities.astype(dtype)
    eligible = level_gamma > 0
    cap_scale = jnp.maximum(cap, jnp.maximum(cap.max(initial=1.0) * 1e-9,
                                             1e-12))

    def headroom(free):
        ratio = jnp.where(demands[:, None, :] > 0,
                          free[None, :, :]
                          / jnp.maximum(demands, 1e-300)[:, None, :], _BIG)
        return jnp.maximum(jnp.where(eligible, ratio.min(axis=2), 0.0), 0.0)

    # gates are RELATIVE to the instance's own magnitudes (mirrors the
    # numpy fill) so a uniformly rescaled problem fills identically
    h_scale = jnp.maximum(headroom(cap).max(initial=0.0), 1e-300)

    def split_of(h, active):
        hsum = h.sum(axis=1)
        s = jnp.where(hsum[:, None] > 0,
                      h / jnp.maximum(hsum[:, None], 1e-300), 0.0)
        return s * active[:, None]

    def slope_of(split):
        task_rate = weights[:, None] * level_gamma * split
        return task_rate, jnp.einsum("nk,nr->kr", task_rate, demands)

    def slope_ref(slope):
        return jnp.maximum(slope.max(initial=0.0), 1e-300)

    def next_dl(slope, free):
        dl = jnp.where(slope > _TOL * slope_ref(slope),
                       free / jnp.maximum(slope, 1e-300), _BIG)
        return dl.min()

    def cond(carry):
        _, _, active, ev = carry
        return active.any() & (ev < k * r_cnt + n + 1)

    def body(carry):
        x, free, active, ev = carry
        h = headroom(free)
        active = active & (h.sum(axis=1) > _TOL * h_scale)
        split = split_of(h, active)
        for _ in range(correctors):
            _, slope = slope_of(split)
            dl = next_dl(slope, free)
            dl = jnp.where(dl < _BIG * 0.5, dl, 0.0)
            h_mid = headroom(jnp.maximum(free - slope * (0.5 * dl), 0.0))
            split = split_of(h_mid, active)
        task_rate, slope = slope_of(split)
        dl = next_dl(slope, free)
        ok = active.any() & (dl < _BIG * 0.5)
        dl = jnp.where(ok, jnp.maximum(dl, 0.0), 0.0)
        x = x + task_rate * dl
        free = jnp.maximum(free - slope * dl, 0.0)
        sat = (free <= _TOL * cap_scale) & (slope > _TOL * slope_ref(slope))
        free = jnp.where(sat, jnp.zeros_like(free), free)
        return x, free, active & ok, ev + 1

    x, _, _, events = jax.lax.while_loop(
        cond, body, (jnp.zeros((n, k), dtype), cap,
                     eligible.any(axis=1), jnp.array(0)))
    return x, events, jnp.array(0.0, dtype)


def _routed_fill(demands, capacities, weights, level_gamma, accel):
    """``_routed_fill_core`` with the sweep's return tuple: under
    ``accel="anderson"`` it appends zero (accel_hits, accel_rejects) — a
    one-shot fill has nothing to accelerate."""
    out = _routed_fill_core(demands, capacities, weights, level_gamma)
    if accel == "anderson":
        zero = jnp.asarray(0, jnp.int32)
        out = out + (zero, zero)
    return out


def _reject_lexmm_traced(placement: str) -> None:
    if placement == "lexmm":
        raise ValueError(
            "placement='lexmm' has no traced baseline fill — its level "
            "increments are certified by host-side LP solves; call "
            "solve_baseline_jax (which routes lexmm through "
            "flowrouter.lexmm_route) or the numpy engine")


def _reject_routed_buckets(placement: str, buckets) -> None:
    """Trace-time gate: the routed headroom fill is not a sweep, so it has
    no bucketed form."""
    if placement == "headroom" and buckets is not None:
        raise ValueError("buckets need the per-server sweep; the routed "
                         "headroom fill is one-shot global — pass "
                         "buckets=None")


@functools.partial(jax.jit, static_argnames=("max_rounds", "placement",
                                             "fill", "round", "accel"))
def baseline_solve_jax(demands, capacities, weights, level_gamma, *, x0=None,
                       max_rounds: int = 256, tol: float = 1e-6,
                       placement: str = "level", fill: str = "event",
                       round: str = "gauss", buckets=None,
                       accel: str = "none"):
    """Solve one exact baseline fill. Returns (x (N,K), rounds, residual).

    ``level_gamma`` is the (N, K) level-rate matrix from
    ``level_rate_matrix`` / ``level_rate_matrix_jnp``. Warm-startable via
    ``x0`` exactly like ``psdsf_solve_jax``; ``fill``/``round``/``accel``
    and ``buckets`` (``None``: the full layout) act exactly as on the
    PS-DSF entry points (the solver body is shared;
    ``accel="anderson"`` appends (accel_hits, accel_rejects)).
    ``placement="headroom"`` runs the routed global fill instead of the
    per-server sweep (one-shot exact; ``x0``, the sweep knobs and the fill
    engine are ignored — the accel axis with it — and ``buckets`` must be
    ``None``); ``"bestfit"`` is numpy-only; ``"lexmm"``'s flow
    certificates are LP solves with data-dependent pivoting — there is
    nothing to trace, so this jitted entry point rejects it
    (``solve_baseline_jax`` routes it host-side instead).
    """
    _check_placement(placement)
    _reject_lexmm_traced(placement)
    _reject_routed_buckets(placement, buckets)
    _check_accel(accel)
    if placement == "headroom":
        return _routed_fill(demands, capacities, weights, level_gamma, accel)
    n, k = level_gamma.shape
    dtype = _solve_dtype(demands)
    if x0 is None:
        x0 = jnp.zeros((n, k), dtype=dtype)
    idx, mask, colours = _layout_arrays(buckets, n, k)
    return _solve_core(demands, capacities, weights, level_gamma,
                       x0.astype(dtype), idx, mask, "rdm", max_rounds, tol,
                       scale=_gamma_scale(demands, capacities, level_gamma),
                       fill=fill, round_mode=round, accel=accel,
                       colours=colours)


@functools.partial(jax.jit, static_argnames=("max_rounds", "placement",
                                             "fill", "round", "accel"))
def baseline_solve_batched(demands, capacities, weights, level_gamma, *,
                           x0=None, max_rounds: int = 256, tol: float = 1e-6,
                           placement: str = "level", fill: str = "event",
                           round: str = "gauss", buckets=None,
                           accel: str = "none"):
    """Solve B independent baseline fills in one jitted vmap call.

    Shapes as ``psdsf_solve_batched``: demands (B, N, R), capacities
    (B, K, R), weights (B, N), level_gamma (B, N, K), optional x0 (B, N, K).
    Pad heterogeneous problems with ``psdsf_jax.batch_problems`` (padding is
    inert: padded users carry level rate 0, padded servers zero capacity).
    ``placement``/``fill``/``round``/``accel`` as in ``baseline_solve_jax``
    (``"lexmm"`` rejected: the flow certificates solve host-side);
    ``buckets`` are per-problem (B, K, Bmax) idx/mask stacks as for
    ``psdsf_solve_batched`` (``None``: the full layout).
    """
    _check_placement(placement)
    _reject_lexmm_traced(placement)
    _reject_routed_buckets(placement, buckets)
    _check_accel(accel)
    b, n, k = level_gamma.shape
    dtype = _solve_dtype(demands)
    if x0 is None:
        x0 = jnp.zeros((b, n, k), dtype=dtype)
    idx, mask = _batch_buckets(buckets, b, n, k)

    def solve(d, c, w, lg, x0_, idx_, mask_):
        if placement == "headroom":
            return _routed_fill(d, c, w, lg, accel)
        return _solve_core(d, c, w, lg, x0_, idx_, mask_, "rdm", max_rounds,
                           tol, scale=_gamma_scale(d, c, lg), fill=fill,
                           round_mode=round, accel=accel)

    return jax.vmap(solve)(demands, capacities, weights, level_gamma,
                           x0.astype(dtype), idx, mask)


def batch_level_rates(problems, mechanism: str, dtype=np.float32):
    """Zero-pad per-problem level-rate matrices to a common (N, K) and stack
    — the ``gamma`` companion of ``psdsf_jax.batch_problems`` for feeding
    ``baseline_solve_batched`` (padding is inert: rate 0 never fills)."""
    n_max = max(p.num_users for p in problems)
    k_max = max(p.num_servers for p in problems)
    lg = np.zeros((len(problems), n_max, k_max), dtype)
    for j, p in enumerate(problems):
        lg[j, :p.num_users, :p.num_servers] = level_rate_matrix(p, mechanism)
    return jnp.asarray(lg)


def solve_baseline_jax(problem: AllocationProblem, mechanism: str, x0=None,
                       max_rounds: int = 256, tol: float = 1e-6,
                       loose_tol: float = 5e-3, placement: str = "level",
                       fill: str = "event", round: str = "gauss",
                       layout: str = "auto", accel: str = "none"
                       ) -> tuple[Allocation, SolveInfo]:
    """Convenience wrapper with the same container/contract as the numpy
    baseline solvers (``solve_tsf`` & co.); ``fill``/``round``/``accel``
    thread to the shared jitted sweep and ``layout`` resolves host-side
    exactly like ``engine.solve`` (bucketed applies to the level sweep only;
    routed / lexmm placements fall back dense under ``"auto"`` and reject an
    explicit ``"bucketed"``).

    ``placement="lexmm"`` is honored here by running the exact flow router
    host-side (``flowrouter.lexmm_route``) — an LP certificate has no XLA
    mirror, and the router is one-shot exact, so there is nothing for the
    jitted sweep to accelerate.
    """
    from .gamma import gamma_matrix
    from .layout import BucketedLayout, resolve_layout
    from .placement import fill_iter_budget

    g = gamma_matrix(problem)    # computed once: level rates AND scale
    lg = level_rate_matrix(problem, mechanism, gamma=g)
    _check_accel(accel)
    swept_placement = placement not in ("headroom", "lexmm")
    if not swept_placement:
        if layout == "bucketed":
            raise ValueError(
                f"layout='bucketed' needs the per-server sweep; placement "
                f"{placement!r} is a one-shot routed fill — use "
                f"layout='dense'")
        resolved = "dense"
        buckets = None
        bucket_max = 0
    else:
        resolved = resolve_layout(layout, support=lg)
        buckets = None
        bucket_max = 0
        if resolved == "bucketed":
            blayout = BucketedLayout.from_support(lg > 0)
            buckets = tuple(jnp.asarray(a) for a in (
                blayout.indices, blayout.mask, blayout.colours))
            bucket_max = blayout.bucket_max
    if placement == "lexmm":
        from .flowrouter import lexmm_route

        x, stages = lexmm_route(problem, lg)
        return (Allocation(problem, x),
                SolveInfo(stages, True, 0.0, placement="lexmm",
                          fill_engine="", accel=accel,
                          stranded_frac=stranded_fraction(problem, x,
                                                          gamma=g)))
    out = baseline_solve_jax(
        jnp.asarray(problem.demands), jnp.asarray(problem.capacities),
        jnp.asarray(problem.weights), jnp.asarray(lg),
        x0=None if x0 is None else jnp.asarray(x0), max_rounds=max_rounds,
        tol=tol, placement=placement, fill=fill, round=round,
        buckets=buckets, accel=accel)
    x, rounds, resid = out[0], out[1], out[2]
    hits, rejects = (int(out[3]), int(out[4])) if accel == "anderson" \
        else (0, 0)
    x = np.asarray(x, dtype=np.float64)
    swept = placement != "headroom"          # routed fill: no per-server fill
    return (Allocation(problem, x),
            SolveInfo.from_residual(int(rounds), float(resid),
                                    float(g.max(initial=1.0)), tol,
                                    loose_tol, placement=placement,
                                    stranded_frac=stranded_fraction(
                                        problem, x, gamma=g),
                                    fill_engine=fill if swept else "",
                                    fill_iters=(int(rounds)
                                                * problem.num_servers
                                                * fill_iter_budget(
                                                    problem.num_resources,
                                                    "rdm", fill)
                                                if swept else 0),
                                    layout=resolved, bucket_max=bucket_max,
                                    accel=accel, accel_hits=hits,
                                    accel_rejects=rejects))
