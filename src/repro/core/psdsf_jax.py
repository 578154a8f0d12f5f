"""Jitted, fully-vectorized PS-DSF solver (RDM and TDM).

Same math as ``psdsf.py`` (server-procedure rebuild to fixed point), expressed
with ``lax`` control flow so the whole solve jits; used by the cluster
scheduler at scale (10^4 users x 10^3 servers ticks) and by the
``kernels/psdsf_vds`` Pallas op for the per-tick VDS reduction.

All loops have static bounds: the inner fill runs exactly R+1 saturation
events; the outer sweep runs ``max_rounds`` with early-exit via
``lax.while_loop`` on the residual.

Two entry points:

* ``psdsf_solve_jax`` — one problem, optional ``x0`` warm start (matches the
  numpy solvers' warm-start contract: same fixed point, fewer rounds).
* ``psdsf_solve_batched`` — B independent problems (per-cell, per-fault-
  scenario, per-what-if) solved in one jitted ``vmap`` call. Heterogeneous
  problem sizes are handled by zero-padding (``batch_problems``): padded
  users carry ``gamma == 0`` (ineligible everywhere -> x == 0) and padded
  servers/resources carry zero capacity (saturated at level 0), so padding
  is exactly inert in the fill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .gamma import gamma_matrix
from .types import Allocation, AllocationProblem

_BIG = 1e30
_TOL = 1e-9


def _fill_one_server_rdm(cap, demands, phi, gamma_i, x_ext):
    """Vectorized equivalent of psdsf.server_fill_rdm. All jnp, no Python
    branching on values. Shapes: cap (R,), demands (N,R), rest (N,).

    The floors are fixed for the whole fill (they depend only on x_ext), so
    users are sorted by floor ONCE; the saturation-event loop then only
    re-masks slopes. Frozen users keep their (zero-slope) breakpoints, which
    subdivides segments without changing the piecewise-linear usage curves,
    so every crossing level is still found — just possibly at a later
    breakpoint index of the same line.
    """
    n, r_cnt = demands.shape
    eligible = gamma_i > 0
    rate = jnp.where(eligible, phi * gamma_i, 0.0)
    floor = jnp.where(eligible, x_ext / jnp.maximum(rate, 1e-300), _BIG)
    order = jnp.argsort(floor)
    f_s = floor[order]                                             # (N,)
    rt_s = rate[order]
    dm_s = demands[order]                                          # (N, R)
    nxt = jnp.concatenate([f_s[1:], jnp.full((1,), _BIG)])[:, None]

    def body(_, carry):
        x_s, active, saturated, frozen_usage, level = carry
        any_active = active.any()
        rate_a = jnp.where(active, rt_s, 0.0)
        slope = dm_s * rate_a[:, None]                             # (N, R)
        cum_slope = jnp.cumsum(slope, axis=0)
        cum_sf = jnp.cumsum(slope * f_s[:, None], axis=0)
        usage_bp = cum_slope * f_s[:, None] - cum_sf + frozen_usage[None, :]
        # candidate crossing level per (breakpoint k, resource r)
        safe_slope = jnp.maximum(cum_slope, 1e-300)
        cand = f_s[:, None] + (cap[None, :] - usage_bp) / safe_slope
        valid = (cum_slope > _TOL) & (cand <= nxt + _TOL)
        cand = jnp.where(valid, jnp.maximum(cand, f_s[:, None]), _BIG)
        lr = cand.min(axis=0)                                      # (R,)
        lr = jnp.where(saturated, _BIG, lr)
        best = lr.min()
        best = jnp.maximum(best, level)
        bind = (lr <= best * (1 + 1e-12) + _TOL) & ~saturated
        new_x = jnp.where(active, rate_a * jnp.maximum(0.0, best - f_s), x_s)
        newly_frozen = active & ((dm_s * bind[None, :]).sum(axis=1) > 0)
        new_frozen_usage = frozen_usage + jnp.einsum(
            "n,nr->r", jnp.where(newly_frozen, new_x, 0.0), dm_s)
        # If nothing is active (or nothing can bind) keep the carry unchanged.
        ok = any_active & (best < _BIG * 0.5)
        x_s = jnp.where(ok, new_x, x_s)
        frozen_usage = jnp.where(ok, new_frozen_usage, frozen_usage)
        saturated = jnp.where(ok, saturated | bind, saturated)
        active = jnp.where(ok, active & ~newly_frozen, active)
        level = jnp.where(ok, best, level)
        return x_s, active, saturated, frozen_usage, level

    cap_scale = jnp.maximum(1.0, cap.max())
    elig_s = eligible[order]
    init = (jnp.zeros(n), elig_s, cap <= _TOL * cap_scale,
            jnp.zeros(r_cnt), 0.0)
    x_s, *_ = jax.lax.fori_loop(0, r_cnt + 1, body, init)
    return jnp.zeros(n, x_s.dtype).at[order].set(x_s)


def _fill_one_server_tdm(demands, phi, gamma_i, x_ext):
    """TDM: single virtual resource sum x/gamma <= 1."""
    del demands
    eligible = gamma_i > 0
    rate = jnp.where(eligible, phi, 0.0)                 # d(x/gamma)/dL
    floor = jnp.where(eligible,
                      x_ext / jnp.maximum(phi * gamma_i, 1e-300), _BIG)
    order = jnp.argsort(floor)
    f_s = floor[order]
    rt_s = rate[order]
    cum_rt = jnp.cumsum(rt_s)
    cum_rf = jnp.cumsum(rt_s * f_s)
    usage_bp = cum_rt * f_s - cum_rf
    cand = f_s + (1.0 - usage_bp) / jnp.maximum(cum_rt, 1e-300)
    nxt = jnp.concatenate([f_s[1:], jnp.full((1,), _BIG)])
    valid = (cum_rt > _TOL) & (cand <= nxt + _TOL)
    level = jnp.where(valid, jnp.maximum(cand, f_s), _BIG).min()
    has = eligible.any()
    x = jnp.where(eligible & has,
                  phi * gamma_i * jnp.maximum(0.0, level - floor), 0.0)
    return x


def _bisect_steps(dtype) -> int:
    """Static bisection-step count by dtype (see ``placement.BISECT_STEPS``):
    48 halvings reach ~3.6e-15 of the initial bracket in f64; past 26 the
    f32 bracket is below ulp and further steps are no-ops."""
    from .placement import BISECT_STEPS, BISECT_STEPS_F32
    return BISECT_STEPS if dtype == jnp.float64 else BISECT_STEPS_F32


def _fill_one_server_rdm_bisect(cap, demands, phi, gamma_i, x_ext):
    """Sort-free twin of ``_fill_one_server_rdm`` via monotone bisection
    (the jitted mirror of ``placement.server_fill_rdm_bisect``).

    Per saturation event the first crossing level is a root of the monotone
    piecewise-linear usage ``U_r(L)``; it is bracketed by [current level,
    max active floor + tightest headroom/total-slope step] and narrowed by
    bisection *only until the bracket contains no active floor breakpoint*
    — on a breakpoint-free bracket every ``U_r`` is linear, so the event
    level is the exact closed-form segment root (tighter than any fixed
    step count; the static ``_bisect_steps`` bound is just the worst-case
    cap). Each probe is one (N,)x(N,R) contraction — no argsort, no cumsum
    breakpoint scan, and no data-dependent indexing, which is what lets
    the Jacobi round mode vmap whole rounds and the ``kernels/psdsf_fill``
    Pallas kernel turn the probe into a server-tiled matmul. The event
    loop itself is a ``while_loop`` that exits as soon as no user is
    active or no resource can bind (typically after 1-2 events, not R+1).
    The bind tolerance is the event engine's level tolerance ``_TOL``
    scaled by the local slope (plus an ulp-guard so the bracket endpoint
    itself always binds); fixed points agree with the event engine to
    root precision (~1e-13, parity-gated).
    """
    n, r_cnt = demands.shape
    dt = demands.dtype
    steps = _bisect_steps(dt)
    eligible = gamma_i > 0
    rate = jnp.where(eligible, phi * gamma_i, 0.0)
    floor = jnp.where(eligible, x_ext / jnp.maximum(rate, 1e-300), _BIG)
    cap_scale = jnp.maximum(1.0, cap.max())
    eps = jnp.asarray(jnp.finfo(dt).eps, dt)
    level_tol = jnp.maximum(jnp.asarray(_TOL, dt), 32 * eps)

    def ev_cond(carry):
        x, active, saturated, frozen_usage, level, ev = carry
        slope_tot = jnp.where(active, rate, 0.0) @ demands
        can_bind = (~saturated) & (slope_tot > _TOL)
        return active.any() & can_bind.any() & (ev < r_cnt + 1)

    def ev_body(carry):
        x, active, saturated, frozen_usage, level, ev = carry
        rate_a = jnp.where(active, rate, 0.0)

        def usage_at(lvl):
            return frozen_usage + (rate_a * jnp.maximum(lvl - floor, 0.0)
                                   ) @ demands

        slope_tot = rate_a @ demands                              # (R,)
        can_bind = (~saturated) & (slope_tot > _TOL)
        lo0 = level
        hi0 = jnp.maximum(jnp.where(active, floor, 0.0).max(), lo0)
        head = jnp.maximum(cap - usage_at(hi0), 0.0)
        step_up = jnp.where(can_bind,
                            head / jnp.maximum(slope_tot, 1e-300), _BIG).min()
        hi_init = hi0 + step_up            # finite: ev_cond ensures can_bind

        def b_cond(lhi):
            lo, hi, it = lhi
            inside = active & (floor > lo) & (floor < hi)
            return inside.any() & (it < steps)

        def b_body(lhi):
            lo, hi, it = lhi
            mid = 0.5 * (lo + hi)
            crossed = jnp.where(can_bind, usage_at(mid) - cap, -1.0).max() >= 0
            return (jnp.where(crossed, lo, mid),
                    jnp.where(crossed, mid, hi), it + 1)

        lo, hi, _ = jax.lax.while_loop(
            b_cond, b_body, (lo0, hi_init, jnp.asarray(0, jnp.int32)))
        # No active floor strictly inside (lo, hi): every U_r is linear on
        # the bracket, so the first crossing is the exact segment root.
        seg_slope = (rate_a * (floor <= lo)) @ demands
        u_lo = usage_at(lo)
        root = lo + jnp.maximum(cap - u_lo, 0.0) / jnp.maximum(seg_slope,
                                                               1e-300)
        root = jnp.where(seg_slope > _TOL, root, _BIG)
        root = jnp.where(u_lo >= cap, lo, root)
        best = jnp.where(can_bind, jnp.minimum(root, hi), _BIG).min()
        best = jnp.maximum(best, level)
        u = usage_at(best)
        lslope = (rate_a * (floor <= best)) @ demands
        bind = can_bind & (cap - u <= lslope * level_tol
                           + 32 * eps * cap_scale)
        x = jnp.where(active, rate_a * jnp.maximum(best - floor, 0.0), x)
        newly_frozen = active & ((demands * bind[None, :]).sum(axis=1) > 0)
        frozen_usage = frozen_usage + jnp.where(newly_frozen, x, 0.0) @ demands
        return (x, active & ~newly_frozen, saturated | bind, frozen_usage,
                best, ev + 1)

    init = (jnp.zeros(n, dt), eligible, cap <= _TOL * cap_scale,
            jnp.zeros(r_cnt, dt), jnp.asarray(0.0, dt),
            jnp.asarray(0, jnp.int32))
    x, *_ = jax.lax.while_loop(ev_cond, ev_body, init)
    return x


def _fill_one_server_tdm_bisect(demands, phi, gamma_i, x_ext):
    """Sort-free TDM fill: one scalar bisection on the single virtual
    time-share resource ``sum_n phi_n max(0, L - f_n) = 1`` (jitted mirror
    of ``placement.server_fill_tdm_bisect``). Bisection stops once the
    bracket is breakpoint-free and the exact linear-segment root finishes
    the solve (``_bisect_steps`` is only the worst-case cap)."""
    del demands
    dt = phi.dtype
    steps = _bisect_steps(dt)
    eligible = gamma_i > 0
    rate = jnp.where(eligible, phi, 0.0)
    floor = jnp.where(eligible,
                      x_ext / jnp.maximum(phi * gamma_i, 1e-300), _BIG)
    has = eligible.any()
    fmax = jnp.where(eligible, floor, 0.0).max()
    hi0 = fmax + 1.0 / jnp.maximum(rate.sum(), 1e-300)

    def b_cond(lhi):
        lo, hi, it = lhi
        inside = eligible & (floor > lo) & (floor < hi)
        return inside.any() & (it < steps)

    def b_body(lhi):
        lo, hi, it = lhi
        mid = 0.5 * (lo + hi)
        crossed = (rate * jnp.maximum(mid - floor, 0.0)).sum() >= 1.0
        return (jnp.where(crossed, lo, mid),
                jnp.where(crossed, mid, hi), it + 1)

    lo, hi, _ = jax.lax.while_loop(
        b_cond, b_body, (jnp.asarray(0.0, dt), hi0,
                         jnp.asarray(0, jnp.int32)))
    seg_slope = (rate * (floor <= lo)).sum()
    u_lo = (rate * jnp.maximum(lo - floor, 0.0)).sum()
    root = lo + jnp.maximum(1.0 - u_lo, 0.0) / jnp.maximum(seg_slope, 1e-300)
    level = jnp.where(seg_slope > _TOL, jnp.minimum(root, hi), hi)
    return jnp.where(eligible & has,
                     phi * gamma_i * jnp.maximum(0.0, level - floor), 0.0)


def _anderson_rounds(one_round, x0, max_rounds, tol, scale, alpha0):
    """Safeguarded limited-memory Anderson mixing over a jitted sweep map —
    the traced twin of ``placement._anderson_fixed_point``, sharing its
    contract: ``one_round(x, alpha) -> (x_new, resid)`` applies ONE full
    damped sweep and reports its full-sweep residual; mixed steps are
    accepted only when one plain sweep from the candidate DECREASES that
    residual, so the certified residual is always a genuine full-sweep
    residual (never the mixer's extrapolated one) and a rejected candidate
    restarts the history from the latest plain pair.

    Where the numpy reference keeps Python lists and calls
    ``numpy.linalg.lstsq``, this keeps fixed-shape rolling history buffers
    (``jnp.roll`` + masked difference columns, history depth
    ``placement.ANDERSON_MEMORY``) and solves the least squares by QR with
    a diagonal guard deactivating dead columns — everything shape-static so
    the whole loop lives inside one ``lax.while_loop`` and vmaps across
    batched problems. Every sweep (plain or safeguard evaluation) counts
    one round, so rounds-to-tol comparisons against ``accel="none"`` are
    sweep-for-sweep honest; a mixing attempt is skipped (masked to a
    no-op) once the round budget cannot afford its evaluation sweep.

    Returns ``(x, rounds, resid, accel_hits, accel_rejects)``.
    """
    from jax.scipy.linalg import solve_triangular

    from .placement import ANDERSON_MEMORY
    # clamp memory below the flattened problem size so the reduced-QR R
    # factor stays square (tiny worked-example instances have size < m);
    # x0.size is a static shape attribute, known at trace time
    m = min(ANDERSON_MEMORY, max(x0.size - 1, 1))
    dt = x0.dtype
    shape = x0.shape
    cols = jnp.arange(m, dtype=jnp.int32)

    def cond(carry):
        _, rounds, _, _, resid = carry[:5]
        return (rounds < max_rounds) & (resid > tol * scale)

    def body(carry):
        x, rounds, prev_norm, alpha, _, hf, hg, hlen, hits, rejects = carry
        g_x, resid_p = one_round(x, alpha)
        f = (g_x - x).ravel()
        hf = jnp.roll(hf, -1, axis=0).at[-1].set(f)
        hg = jnp.roll(hg, -1, axis=0).at[-1].set(g_x.ravel())
        hlen = jnp.minimum(hlen + 1, m + 1)
        rounds = rounds + 1
        can_mix = ((hlen >= 2) & (resid_p > tol * scale)
                   & (rounds < max_rounds))
        # difference columns over the valid window; rolled-in slots beyond
        # the history length are masked to exact zeros (dead columns)
        col_ok = (cols >= (m + 1 - hlen)).astype(dt)
        df = (hf[1:] - hf[:-1]).T * col_ok[None, :]
        dg = (hg[1:] - hg[:-1]).T * col_ok[None, :]
        q, r = jnp.linalg.qr(df)
        diag = jnp.abs(jnp.diagonal(r))
        ref = jnp.maximum(diag.max(), jnp.asarray(1e-30, dt))
        # dead/degenerate columns get an O(scale) diagonal so the solve
        # stays finite; their dG columns are zero (or the safeguard
        # rejects), so the inflated theta components are inert
        r = r + jnp.diag(jnp.where(diag < 1e-12 * ref, ref,
                                   jnp.asarray(0.0, dt)))
        theta = solve_triangular(r, q.T @ f, lower=False)
        cand = jnp.maximum(hg[-1] - dg @ theta, 0.0).reshape(shape)
        g_c, resid_c = one_round(cand, alpha)
        accept = can_mix & jnp.isfinite(resid_c) & (resid_c < resid_p)
        reject = can_mix & ~accept
        rounds = jnp.where(can_mix, rounds + 1, rounds)
        hf_acc = jnp.roll(hf, -1, axis=0).at[-1].set((g_c - cand).ravel())
        hg_acc = jnp.roll(hg, -1, axis=0).at[-1].set(g_c.ravel())
        hf = jnp.where(accept, hf_acc, hf)
        hg = jnp.where(accept, hg_acc, hg)
        hlen = jnp.where(accept, jnp.minimum(hlen + 1, m + 1),
                         jnp.where(reject, jnp.asarray(1, jnp.int32), hlen))
        x_next = jnp.where(accept, g_c, g_x)
        resid = jnp.where(accept, resid_c, resid_p)
        hits = hits + accept.astype(jnp.int32)
        rejects = rejects + reject.astype(jnp.int32)
        # the plain core's alpha-normalized stall schedule, without its
        # drift test: the mixer extrapolates a repeating step by itself
        norm = resid / alpha
        stall = (rounds >= 3) & (norm > 0.9 * prev_norm) & (alpha > 0.01)
        alpha = jnp.where(stall, alpha * 0.7, alpha)
        return (x_next, rounds, norm, alpha, resid, hf, hg, hlen, hits,
                rejects)

    big = jnp.array(jnp.inf, dtype=dt)
    zeros_h = jnp.zeros((m + 1, x0.size), dt)
    x, rounds, _, _, resid, _, _, _, hits, rejects = jax.lax.while_loop(
        cond, body,
        (x0, jnp.array(0), big, jnp.array(alpha0, dt), big, zeros_h,
         zeros_h, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
         jnp.asarray(0, jnp.int32)))
    return x, rounds, resid, hits, rejects


def _check_accel(accel: str) -> None:
    """Trace-time gate for the ``accel`` axis shared by the jitted entry
    points (the numpy sweeps validate against the same
    ``placement.ACCEL_ENGINES`` tuple)."""
    if accel not in ("none", "anderson"):
        raise ValueError(f"accel must be 'none' or 'anderson': {accel!r}")


def _server_fill(mode, fill):
    """The per-server fill engine for ``mode`` x ``fill``, as one signature
    ``f(cap, demands, phi, gamma_i, x_ext)`` (the TDM fills ignore
    ``cap``): ``"event"`` is the argsort + saturation-event scan,
    ``"bisect"`` the sort-free monotone bisection (same fixed point — see
    ``_fill_one_server_rdm_bisect``)."""
    if mode not in ("rdm", "tdm"):
        raise ValueError(f"mode must be 'rdm' or 'tdm': {mode!r}")
    if fill not in ("event", "bisect"):
        raise ValueError(f"fill must be 'event' or 'bisect': {fill!r}")
    bisect = fill == "bisect"
    if mode == "rdm":
        return _fill_one_server_rdm_bisect if bisect else _fill_one_server_rdm
    f = _fill_one_server_tdm_bisect if bisect else _fill_one_server_tdm
    return lambda cap, demands, phi, gamma_i, x_ext: f(demands, phi, gamma_i,
                                                       x_ext)


def _full_layout(n, k):
    """The full bucket layout of an (N, K) problem: every server's bucket
    holds every user, in order — what ``layout.BucketedLayout.from_support``
    builds from an all-ones support, here built in the trace from the
    shapes alone. ``buckets=None`` means this layout everywhere.

    The barrier hides that the indices are an iota. XLA would otherwise
    simplify around it into another program, whose answers drift from a
    host-built full layout's in the last bits."""
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (k, n))
    return jax.lax.optimization_barrier((idx, jnp.ones((k, n), dtype=bool)))


def _gather(x, idx, mask):
    """Dense (N, K) -> padded (K, Bmax) buckets (padding zeroed)."""
    return jnp.where(mask, jnp.take_along_axis(x.T, idx, axis=1), 0.0)


def _scatter(xb, idx, mask, n):
    """Padded (K, Bmax) buckets -> dense (N, K). A scatter-ADD, not a set:
    a row's real ids are distinct, but batch-padded buckets replicate id 0
    in the padding, and a colliding ``.set`` picks an unspecified writer —
    masked padding adds an exact 0.0 instead."""
    k = idx.shape[0]
    cols = jnp.broadcast_to(jnp.arange(k, dtype=idx.dtype)[:, None],
                            idx.shape)
    return jnp.zeros((n, k), xb.dtype).at[idx, cols].add(
        jnp.where(mask, xb, 0.0))


def _row_sums(xb, idx, mask, n):
    """Per-user totals (N,) of a (K, Bmax) bucket tensor."""
    return jnp.zeros(n, xb.dtype).at[idx.ravel()].add(
        jnp.where(mask, xb, 0.0).ravel())


def _bucket_fill(mode, fill, demands, capacities, weights, gamma, idx, mask):
    """Gather each server's bucket of demands, weights and gamma (padding
    carries gamma 0, so it always fills to zero) and return
    ``fill_server(i, x_ext)``: server i's fill over its bucket."""
    f = _server_fill(mode, fill)
    gam_b = _gather(gamma, idx, mask)
    dem_b = demands[idx]                                   # (K, Bmax, R)
    phi_b = weights[idx]                                   # (K, Bmax)

    def fill_server(i, x_ext):
        return f(capacities[i], dem_b[i], phi_b[i], gam_b[i], x_ext)

    return fill_server


def _gauss_round(fill_server, idx, mask, sweep, n, xb, alpha):
    """One damped Gauss-Seidel round over the server classes ``sweep``, a
    (C, W) array of server ids padded with the sentinel K, class by class.
    The servers of a class share no user: a fill reads and writes only its
    own users' row sums, so one vmapped fill of the whole class gives what
    filling them one after another does. A round is thus a Gauss-Seidel
    round in class-major order, C serial steps; a (K, 1) sweep is the
    one-server-a-step round. Row sums are re-derived from the buckets at
    the round start, then kept up to date by scatter-adds of each class's
    deltas (distinct users, so the adds never collide). Sentinel slots are
    inert: their reads are masked, their row write dropped and their delta
    an exact 0. Returns the new buckets and the round's max |delta|."""
    k = idx.shape[0]
    fill_class = jax.vmap(fill_server)
    xsum = _row_sums(xb, idx, mask, n)

    def per_class(c, carry):
        xb, xsum, resid = carry
        srv = sweep[c]                                      # (W,)
        i = jnp.minimum(srv, k - 1)
        m = mask[i] & (srv < k)[:, None]                    # (W, Bmax)
        u = idx[i]
        x_ext = xsum[u] - xb[i]
        xi = jnp.where(m, fill_class(i, x_ext), 0.0)
        xi = (1.0 - alpha) * xb[i] + alpha * xi
        delta = jnp.where(m, xi - xb[i], 0.0)
        return (xb.at[srv].set(jnp.where(m, xi, 0.0), mode="drop"),
                xsum.at[u].add(delta),
                jnp.maximum(resid, jnp.abs(delta).max()))

    xb, _, resid = jax.lax.fori_loop(
        0, sweep.shape[0], per_class,
        (xb, xsum, jnp.asarray(0.0, xb.dtype)))
    return xb, resid


def _layout_arrays(buckets, n, k):
    """``(idx, mask, colours)`` of a jitted entry point's ``buckets``:
    ``None`` is the full layout, where every server conflicts with every
    other (``colours`` None: one server a step); an ``(idx, mask)`` pair
    sweeps one server a step too; ``(idx, mask, colours)`` (a
    ``layout.BucketedLayout``'s three arrays) fills each class at once."""
    if buckets is None:
        return _full_layout(n, k) + (None,)
    return tuple(buckets) + (None,) * (3 - len(buckets))


def _solve_core(demands, capacities, weights, gamma, x0, idx, mask, mode,
                max_rounds, tol, servers=None, alpha0=1.0, scale=None,
                fill="event", round_mode="gauss", accel="none",
                colours=None):
    """Traced solver body shared by every jitted entry point.

    All array arguments are positional so ``jax.vmap`` maps over them
    directly; ``mode``/``max_rounds``/``tol`` close over the trace.

    ``idx``/``mask`` are padded (K, Bmax) per-server user buckets: a
    host-built ``layout.BucketedLayout``'s arrays for a sparse support, or
    ``_full_layout`` (every server holds every user) for a dense problem.
    The whole solve runs on gathered (K, Bmax[, R]) bucket arrays, so a
    round costs O(nnz*R) — O(N*K*R) on the full layout. Padded slots carry
    gamma 0, so fills return 0 for them and their deltas are exact zeros:
    padding is inert in fills, row sums and the residual (the same trick as
    ``batch_problems``).

    ``scale`` overrides the residual-acceptance scale (defaults to
    ``gamma.max()`` — right for PS-DSF where gamma is the per-server
    monopolization; baseline fills pass the per-server gamma scale
    explicitly because their level-rate "gamma" sums over servers).

    ``servers`` (optional int32 vector) restricts each sweep to those
    servers — the incremental/event-driven mode: after churn touches a few
    cells, only their servers need re-filling, the rest of the fleet keeps
    its fixed point. Callers restricting the sweep should verify with a full
    sweep afterwards (``psdsf_resolve_batched`` does).

    ``fill`` selects the per-server fill engine (``_server_fill``).

    ``round_mode`` selects the outer iteration: ``"gauss"`` (the
    Gauss-Seidel ``fori`` over server classes, ``_gauss_round``) or
    ``"jacobi"`` —
    every server fills against the PREVIOUS round's usage simultaneously,
    so one round is a single vmapped fill over the server axis (the
    vectorization the sequential ``fori`` blocks). Jacobi trades per-round
    progress for parallel width and oscillates more than Gauss-Seidel on
    coupled instances, so it starts pre-damped (alpha <= 0.5) and leans on
    the same stall schedule; fixed points are identical where both converge.

    The rebuild map has small limit cycles on large instances (the paper
    leaves sweep convergence open, footnote 5); residuals stall ~0.1% of
    scale with undamped sweeps. Damping x <- (1-a) x + a rebuild(x) shrinks
    the cycle amplitude proportionally to ``a``, so the schedule lets ``a``
    fall to 0.01 (a 100x residual reduction) once the residual stops
    contracting; exact small instances converge before any damping starts.
    A flat residual is not always a cycle: where the rebuild map is a
    translation, or contracts slowly along one direction, damping only
    slows the approach, so such a step lifts the damping (see ``body``).

    ``accel="anderson"`` wraps the damped sweep in safeguarded Anderson
    mixing (``_anderson_rounds``); the mixing state is the packed
    (K, Bmax) bucket tensor, so history memory scales with nnz, not N*K.

    Returns (x dense (N, K), rounds, residual), plus (accel_hits,
    accel_rejects) under ``accel="anderson"``.
    """
    scale = jnp.maximum(1.0, gamma.max() if scale is None else scale)
    n, k = gamma.shape
    dt = x0.dtype
    if servers is not None and colours is not None:
        raise ValueError("a servers= restriction sweeps one server a step; "
                         "pass no colours with it")
    sweep = jnp.arange(k, dtype=jnp.int32) if servers is None else servers
    if round_mode not in ("gauss", "jacobi"):
        raise ValueError(
            f"round must be 'gauss' or 'jacobi': {round_mode!r}")
    _check_accel(accel)

    fill_server = _bucket_fill(mode, fill, demands, capacities, weights,
                               gamma, idx, mask)
    xb0 = _gather(x0, idx, mask)

    if round_mode == "jacobi":
        alpha0 = min(alpha0, 0.5)
        fill_all = jax.vmap(fill_server, in_axes=(0, 0))

        def one_round(xb, alpha):
            xsum = _row_sums(xb, idx, mask, n)
            x_ext = xsum[idx[sweep]] - xb[sweep]
            xi = jnp.where(mask[sweep], fill_all(sweep, x_ext), 0.0)
            new = (1.0 - alpha) * xb[sweep] + alpha * xi
            resid = jnp.abs(new - xb[sweep]).max()
            return xb.at[sweep].set(new), resid
    else:
        classes = sweep[:, None] if colours is None else colours

        def one_round(xb, alpha):
            return _gauss_round(fill_server, idx, mask, classes, n, xb,
                                alpha)

    if accel == "anderson":
        xb, rounds, resid, hits, rejects = _anderson_rounds(
            one_round, xb0, max_rounds, tol, scale, alpha0)
        stats = (hits, rejects)
    else:
        def cond(carry):
            _, rounds, _, _, resid, _, _ = carry
            return (rounds < max_rounds) & (resid > tol * scale)

        def body(carry):
            xb, rounds, prev_norm, alpha, _, prev_step, calm = carry
            xb_new, resid = one_round(xb, alpha)
            # Stall detection on the ALPHA-NORMALIZED residual: on a limit
            # cycle resid ~ alpha * amplitude, so resid/alpha stays flat
            # (shrink every round of the descent), while true contraction
            # shrinks it (never damp a converging sweep). A flat residual
            # is not always a cycle. Along a step that keeps its direction
            # the undamped multiplier is mu = 1 - (1 - ratio) / alpha, with
            # ratio = norm / prev_norm. A mode that damping holds (mu <= -1)
            # contracts by 1 - 2 alpha or faster, so a ratio above
            # max(0.9, 1 - alpha/2) (mu > 1/2) is a monotone approach that
            # damping only slows. Such a step lifts the damping, and the
            # detector waits 3 rounds as at the start, when it repeats the
            # last one (a translation: turn <= 1e-3 alpha norm) or, at the
            # damping floor, turns no more than a smooth damped flow does
            # (turn <= alpha norm; a sweep chattering across a fill's
            # switch turns by O(norm)).
            norm = resid / alpha
            step = (xb_new - xb) / alpha
            turn = jnp.abs(step - prev_step * (norm / prev_norm)).max()
            slow = norm > jnp.maximum(0.9, 1.0 - 0.5 * alpha) * prev_norm
            at_floor = (alpha <= 0.01) & (rounds >= calm + 3)
            lift = slow & ((turn <= 1e-3 * alpha * norm)
                           | (at_floor & (turn <= alpha * norm)))
            stall = ((rounds >= calm + 3) & (norm > 0.9 * prev_norm)
                     & (alpha > 0.01))
            alpha = jnp.where(lift, alpha0,
                              jnp.where(stall, alpha * 0.7, alpha))
            calm = jnp.where(lift, rounds + 1, calm)
            return xb_new, rounds + 1, norm, alpha, resid, step, calm

        big = jnp.array(jnp.inf, dtype=dt)
        xb, rounds, _, _, resid, _, _ = jax.lax.while_loop(
            cond, body, (xb0, jnp.array(0), big, jnp.array(alpha0, dt), big,
                         jnp.zeros_like(xb0), jnp.array(0)))
        stats = ()
    return (_scatter(xb, idx, mask, n), rounds, resid) + stats


def _solve_dtype(demands):
    return jnp.float64 if demands.dtype == jnp.float64 else jnp.float32


# ---------------------------------------------------------------------------
# Placement mirrors: stranded fraction, repack-and-refill (headroom)
# ---------------------------------------------------------------------------

def stranded_fraction_jnp(demands, capacities, gamma, x):
    """jnp twin of ``placement.stranded_fraction``: fraction of demandable
    capacity (cap > 0 and some eligible user demands the resource) left
    unused by ``x``."""
    dt = x.dtype
    wanted = (gamma > 0).astype(dt).T @ (demands > 0).astype(dt)
    mask = ((capacities > 0) & (wanted > 0)).astype(dt)
    total = (capacities * mask).sum()
    usage = jnp.einsum("nk,nr->kr", x, demands)
    used = (usage * mask).sum()
    frac = 1.0 - jnp.minimum(used / jnp.maximum(total, 1e-300), 1.0)
    return jnp.where(total > 0, frac, 0.0)


def _repack_core(x, demands, capacities, weights, level_gamma, mode):
    """jnp twin of ``placement.repack_pass`` (proportional rule only —
    bestfit's greedy repack is numpy-only): drain each user largest-first
    and re-split its total across eligible servers in proportion to the
    freed headroom. Totals are preserved exactly; the proportional split is
    feasible whenever the drained placement was (kept unchanged otherwise).
    """
    del weights   # the repack moves tasks; rates don't enter
    n, k = x.shape
    eligible = level_gamma > 0
    if mode == "rdm":
        free0 = capacities - jnp.einsum("nk,nr->kr", x, demands)
    else:
        inv_g = jnp.where(eligible,
                          1.0 / jnp.maximum(level_gamma, 1e-300), 0.0)
        free0 = 1.0 - jnp.einsum("nk,nk->k", x, inv_g)       # (K,) share slack
    order = jnp.argsort(-x.sum(axis=1), stable=True)

    def body(j, carry):
        x, free = carry
        u = order[j]
        xu = x[u]
        du = demands[u]
        if mode == "rdm":
            free = free + xu[:, None] * du[None, :]                # drain
            ratio = jnp.where(du[None, :] > 0,
                              free / jnp.maximum(du, 1e-300)[None, :], _BIG)
            h = jnp.where(eligible[u], ratio.min(axis=1), 0.0)
        else:
            free = free + xu * inv_g[u]
            h = jnp.where(eligible[u],
                          level_gamma[u] * jnp.maximum(free, 0.0), 0.0)
        h = jnp.maximum(h, 0.0)
        t_u = xu.sum()
        hs = h.sum()
        xnew = jnp.where((t_u > 0) & (hs >= t_u),
                         t_u * h / jnp.maximum(hs, 1e-300), xu)
        free = (free - xnew[:, None] * du[None, :] if mode == "rdm"
                else free - xnew * inv_g[u])
        return x.at[u].set(xnew), free

    x, _ = jax.lax.fori_loop(0, n, body, (x, free0))
    return x


def _repack_refill_core(demands, capacities, weights, gamma, x, rounds,
                        resid, mode, max_rounds, tol, passes=3,
                        min_gain=1e-6, loose_tol=5e-3, fill="event",
                        round_mode="gauss"):
    """Headroom placement for PS-DSF: improve a level fixed point with up to
    ``passes`` repack + warm-refill rounds, keeping a round only when the
    refill re-certifies and the stranded fraction measurably drops (the
    jnp mirror of ``placement.repack_refill``). Acceptance matches the
    numpy contract — tight OR loose convergence counts (``SolveInfo``'s
    ``converged`` includes ``approx``), so limit-cycling instances accept
    the same refills on both backends. The refills sweep the full layout
    (the repack moves tasks anywhere a user is eligible). Returns the
    accepted (x, rounds, resid)."""
    scale = jnp.maximum(1.0, gamma.max())
    s0 = stranded_fraction_jnp(demands, capacities, gamma, x)
    idx, mask = _full_layout(*gamma.shape)

    def body(_, carry):
        x_b, s_b, rounds_b, resid_b = carry
        xr = _repack_core(x_b, demands, capacities, weights, gamma, mode)
        x2, r2, res2 = _solve_core(demands, capacities, weights, gamma, xr,
                                   idx, mask, mode, max_rounds, tol,
                                   fill=fill, round_mode=round_mode)
        s2 = stranded_fraction_jnp(demands, capacities, gamma, x2)
        accept_tol = jnp.maximum(tol, loose_tol)
        ok = (res2 <= accept_tol * scale) & (s2 < s_b - min_gain)
        return (jnp.where(ok, x2, x_b), jnp.where(ok, s2, s_b),
                jnp.where(ok, r2, rounds_b), jnp.where(ok, res2, resid_b))

    x, _, rounds, resid = jax.lax.fori_loop(
        0, passes, body, (x, s0, rounds, resid))
    return x, rounds, resid


def _check_placement(placement: str) -> None:
    """Trace-time gate shared by the jitted entry points. ``lexmm`` passes:
    for the PS-DSF regimes it is the identity on the level solve, so the
    jitted paths realize it exactly (the flow certificates only exist for
    the global-share mechanisms, whose jitted twins gate it themselves)."""
    from .placement import get_placement
    if not get_placement(placement).jax_backend:
        raise ValueError(f"placement {placement!r} has no jitted mirror "
                         f"(numpy engine only)")


@functools.partial(jax.jit,
                   static_argnames=("mode", "max_rounds", "placement",
                                    "fill", "round", "accel"))
def psdsf_solve_jax(demands, capacities, weights, gamma, *, x0=None,
                    mode: str = "rdm", max_rounds: int = 256,
                    tol: float = 1e-6, placement: str = "level",
                    fill: str = "event", round: str = "gauss",
                    buckets=None, accel: str = "none"):
    """Solve PS-DSF. Returns (x (N,K), rounds, residual) — plus
    (accel_hits, accel_rejects) when ``accel="anderson"``.

    ``gamma`` is the (N, K) eligibility-masked monopolization matrix; compute
    it with ``repro.core.gamma_matrix`` (or its jnp twin below). Damping
    uses the alpha-normalized stall schedule of ``_solve_core`` (floor
    0.01) — deeper than the numpy solver's (floor 0.15), so on
    limit-cycling instances this solver accepts at ~15x smaller residuals
    and round counts differ; fixed points agree where they exist.

    ``x0`` (N, K) warm-starts the sweep (e.g. the pre-churn fixed point);
    the rebuild map's fixed points do not depend on the starting point, so a
    warm start changes only the round count, not the solution.

    ``fill`` selects the per-server fill engine (``"event"``/``"bisect"``)
    and ``round`` the outer iteration (``"gauss"``/``"jacobi"``) — see
    ``_solve_core``; the bisect fill is the sort-free engine the
    ``fill_comparison`` benchmark gates at >= 3x over the event fill on the
    dense pinned instance, and damped Jacobi is its whole-cluster vmapped
    round. Both default to the historical engines.

    ``placement="headroom"`` follows the level solve with jitted
    repack-and-refill passes (``_repack_refill_core``); ``"lexmm"`` is the
    identity on the level solve (PS-DSF's per-server fill is already the
    per-server lexicographic optimum — see ``flowrouter``); ``"bestfit"``
    is numpy-only and rejected here.

    ``buckets=(idx, mask, colours)`` (a host-built
    ``layout.BucketedLayout``'s padded arrays and server colouring) sweeps
    each server's eligibility bucket only — O(nnz) a round, same fixed
    point — and fills each colour class at once; an ``(idx, mask)`` pair
    fills one server a step. ``None`` is the full layout (every server
    holds every user; one server a step). The headroom repack sweeps the
    full layout either way.

    ``accel="anderson"`` runs the safeguarded Anderson-mixed outer
    iteration (``_anderson_rounds``) and extends the return tuple with
    (accel_hits, accel_rejects); the headroom repack refills stay plain —
    they are warm re-sweeps already at the fixed point, where mixing has
    nothing to extrapolate.
    """
    _check_placement(placement)
    n, k = gamma.shape
    dtype = _solve_dtype(demands)
    if x0 is None:
        x0 = jnp.zeros((n, k), dtype=dtype)
    idx, mask, colours = _layout_arrays(buckets, n, k)
    out = _solve_core(demands, capacities, weights, gamma, x0.astype(dtype),
                      idx, mask, mode, max_rounds, tol, fill=fill,
                      round_mode=round, accel=accel, colours=colours)
    if placement == "headroom":
        fixed = _repack_refill_core(demands, capacities, weights, gamma,
                                    *out[:3], mode, max_rounds, tol,
                                    fill=fill, round_mode=round)
        out = fixed + out[3:]
    return out


def _batch_buckets(buckets, b, n, k):
    """Per-problem (B, K, Bmax) bucket stacks for the batched entries.
    ``None`` is the full layout, broadcast over the batch: shared through
    ``vmap(in_axes=None)`` it compiles to another program, whose answers
    drift from a host-built full layout's in the last bits."""
    if buckets is not None:
        return buckets
    return tuple(jnp.broadcast_to(a, (b,) + a.shape)
                 for a in _full_layout(n, k))


@functools.partial(jax.jit,
                   static_argnames=("mode", "max_rounds", "placement",
                                    "fill", "round", "accel"))
def psdsf_solve_batched(demands, capacities, weights, gamma, *, x0=None,
                        mode: str = "rdm", max_rounds: int = 256,
                        tol: float = 1e-6, placement: str = "level",
                        fill: str = "event", round: str = "gauss",
                        buckets=None, accel: str = "none"):
    """Solve B independent PS-DSF problems in one jitted call.

    Shapes: demands (B, N, R), capacities (B, K, R), weights (B, N),
    gamma (B, N, K), optional x0 (B, N, K). Returns (x (B, N, K),
    rounds (B,), residual (B,)) — per-problem round counts are exact (a
    converged problem's carry stops updating under the vmapped while_loop).

    Pad heterogeneous problems with ``batch_problems``; padding is inert
    (see module docstring). ``placement``/``fill``/``round``/``accel`` as
    in ``psdsf_solve_jax`` (``accel="anderson"`` appends per-problem
    (accel_hits, accel_rejects) vectors to the return tuple).
    ``buckets`` are per-problem (B, K, Bmax) idx/mask stacks (pad each
    problem's layout to a common Bmax with masked slots; padding is inert
    like the user/server padding); ``None`` is the full layout.
    """
    _check_placement(placement)
    b, n, k = gamma.shape
    dtype = _solve_dtype(demands)
    if x0 is None:
        x0 = jnp.zeros((b, n, k), dtype=dtype)
    idx, mask = _batch_buckets(buckets, b, n, k)

    def solve(d, c, w, g, x0_, idx_, mask_):
        out = _solve_core(d, c, w, g, x0_, idx_, mask_, mode, max_rounds,
                          tol, fill=fill, round_mode=round, accel=accel)
        if placement == "headroom":
            fixed = _repack_refill_core(d, c, w, g, *out[:3], mode,
                                        max_rounds, tol, fill=fill,
                                        round_mode=round)
            out = fixed + out[3:]
        return out

    return jax.vmap(solve)(demands, capacities, weights, gamma,
                           x0.astype(dtype), idx, mask)


@functools.partial(jax.jit,
                   static_argnames=("mode", "max_rounds", "placement",
                                    "fill", "round", "accel"))
def psdsf_resolve_batched(demands, capacities, weights, gamma, x0, servers, *,
                          mode: str = "rdm", max_rounds: int = 64,
                          tol: float = 1e-4, placement: str = "level",
                          fill: str = "event", round: str = "gauss",
                          buckets=None, accel: str = "none"):
    """Event-driven incremental re-solve of B perturbed problems.

    ``servers`` (B, S) int32 lists the servers each scenario's events touch
    (degraded servers + every server an arriving/departing user is eligible
    on; pad rows by repeating any listed index — refilling an unaffected
    server is idempotent). Phase 1 sweeps only those servers from the warm
    start ``x0`` (B, N, K); phase 2 self-certifies with full sweeps until
    the GLOBAL residual passes ``tol``, so a ripple that escapes the
    restricted set is caught, not silently dropped.

    Returns (x, rounds_restricted, rounds_full, residual); the residual is
    the full-sweep one. Cost ~ S/K per restricted round, which is where the
    engine's throughput over cold full solves comes from.

    ``placement="headroom"`` appends repack-and-refill passes after the
    verification sweep (full sweeps — the repack is global by nature).
    ``fill``/``round`` select the fill engine and outer iteration for both
    phases, as in ``psdsf_solve_jax``; ``buckets`` ((B, K, Bmax) stacks, or
    ``None`` for the full layout) serve BOTH the restricted and the
    verification phase — the restricted+verify exactness contract is
    layout-independent. ``accel="anderson"`` runs the
    safeguarded Anderson mixer in BOTH phases and appends summed
    (accel_hits, accel_rejects) to the return tuple — this is where the
    axis pays off most: a warm re-solve near a limit cycle finally
    contracts instead of re-orbiting.
    """
    _check_placement(placement)

    def one(d, c, w, g, x0_, srv, idx_, mask_):
        def core(x_init, servers=None, alpha0=1.0):
            return _solve_core(d, c, w, g, x_init, idx_, mask_, mode,
                               max_rounds, tol, servers=servers,
                               alpha0=alpha0, fill=fill, round_mode=round,
                               accel=accel)

        # The warm start is near the fixed point; alpha0 = 0.3 is enough to
        # absorb a cell-local perturbation in a few sweeps without fully
        # re-exciting the restricted subproblem's limit cycle.
        out1 = core(x0_, servers=srv, alpha0=0.3)
        x, r_restricted = out1[0], out1[1]
        # Verification starts pre-damped at alpha ~ the level where a cold
        # solve's own schedule accepts (resid ~ alpha * cycle amplitude
        # crosses tol around alpha ~ 0.02 at scheduler tolerance), so
        # incremental and cold solves end with equal-strength certificates;
        # an undamped full sweep here would just re-excite the limit cycle.
        out2 = core(x, alpha0=0.02)
        x, r_full, resid = out2[0], out2[1], out2[2]
        if placement == "headroom":
            x, r_full, resid = _repack_refill_core(
                d, c, w, g, x, r_full, resid, mode, max_rounds, tol,
                fill=fill, round_mode=round)
        if accel == "anderson":
            return (x, r_restricted, r_full, resid,
                    out1[3] + out2[3], out1[4] + out2[4])
        return x, r_restricted, r_full, resid

    idx, mask = _batch_buckets(buckets, *gamma.shape)
    return jax.vmap(one)(demands, capacities, weights, gamma,
                         x0.astype(_solve_dtype(demands)), servers, idx, mask)


def batch_problems(problems, dtype=np.float32):
    """Zero-pad a sequence of ``AllocationProblem`` to a common (N, K, R) and
    stack for ``psdsf_solve_batched``.

    Returns dict with keys demands (B,N,R), capacities (B,K,R), weights
    (B,N), gamma (B,N,K), sizes [(n_i, k_i)]. Padded users get weight 1 and
    gamma 0 (never allocated); padded servers/resources get zero capacity.
    """
    n_max = max(p.num_users for p in problems)
    k_max = max(p.num_servers for p in problems)
    r_max = max(p.num_resources for p in problems)
    b = len(problems)
    demands = np.zeros((b, n_max, r_max), dtype)
    capacities = np.zeros((b, k_max, r_max), dtype)
    weights = np.ones((b, n_max), dtype)
    gamma = np.zeros((b, n_max, k_max), dtype)
    sizes = []
    for j, p in enumerate(problems):
        n, k, r = p.num_users, p.num_servers, p.num_resources
        demands[j, :n, :r] = p.demands
        capacities[j, :k, :r] = p.capacities
        weights[j, :n] = p.weights
        gamma[j, :n, :k] = gamma_matrix(p)
        sizes.append((n, k))
    return dict(demands=jnp.asarray(demands),
                capacities=jnp.asarray(capacities),
                weights=jnp.asarray(weights), gamma=jnp.asarray(gamma),
                sizes=sizes)


def unbatch_solutions(x, problems):
    """Slice a padded (B, N, K) solution back into per-problem Allocations."""
    out = []
    for j, p in enumerate(problems):
        out.append(Allocation(
            p, np.asarray(x[j, :p.num_users, :p.num_servers],
                          dtype=np.float64)))
    return out


def gamma_matrix_jnp(demands, capacities, eligibility):
    """jnp twin of gamma.gamma_matrix (for end-to-end jitted pipelines)."""
    d = demands
    ratio = jnp.where(d[:, None, :] > 0,
                      capacities[None, :, :] / jnp.maximum(d[:, None, :], 1e-300),
                      _BIG)
    g = ratio.min(axis=2)
    g = jnp.where(g >= _BIG * 0.5, 0.0, g)
    return g * eligibility


def solve_psdsf_rdm_jax(problem: AllocationProblem, x0=None,
                        max_rounds: int = 64, fill: str = "event",
                        round: str = "gauss",
                        accel: str = "none") -> Allocation:
    """Convenience wrapper producing the same container as the numpy solver
    (``fill``/``round``/``accel`` select the fill engine, outer iteration
    and outer-iteration accelerator)."""
    g = gamma_matrix(problem)
    x, *_ = psdsf_solve_jax(
        jnp.asarray(problem.demands), jnp.asarray(problem.capacities),
        jnp.asarray(problem.weights), jnp.asarray(g),
        x0=None if x0 is None else jnp.asarray(x0),
        mode="rdm", max_rounds=max_rounds, fill=fill, round=round,
        accel=accel)
    return Allocation(problem, np.asarray(x, dtype=np.float64))


def solve_psdsf_tdm_jax(problem: AllocationProblem, x0=None,
                        max_rounds: int = 64, fill: str = "event",
                        round: str = "gauss",
                        accel: str = "none") -> Allocation:
    """PS-DSF under time-division multiplexing on the jitted jax backend
    (continuous task fractions; RDM variant is ``solve_psdsf_rdm_jax``)."""
    g = gamma_matrix(problem)
    x, *_ = psdsf_solve_jax(
        jnp.asarray(problem.demands), jnp.asarray(problem.capacities),
        jnp.asarray(problem.weights), jnp.asarray(g),
        x0=None if x0 is None else jnp.asarray(x0),
        mode="tdm", max_rounds=max_rounds, fill=fill, round=round,
        accel=accel)
    return Allocation(problem, np.asarray(x, dtype=np.float64))
