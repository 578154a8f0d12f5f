"""Plain numpy PS-DSF (RDM), written from the paper and independent of the
program: it imports nothing of it and takes nothing it has made.

It decides ``correct``. For an allocation ``x`` (N, K) that the program
certified, it reads three numbers:

* ``capacity_excess``: the largest use of a server resource beyond its
  capacity, as a share of that capacity.
* ``saturation_gap``: on each server with an eligible user, how far the
  fullest resource is from exactly full, the largest over servers.
* ``vds_rel_err``: the program's telemetry minimum (Eq. 16) against the
  same minimum computed here, relative.

``rounding`` makes the fill compute in a lower precision: the control
puts this reference in the program's place with every intermediate
rounded to bfloat16, the step below the float32 the configurations state.
"""
from __future__ import annotations

import numpy as np


def exact(a):
    """float64 arithmetic (no rounding)."""
    return np.asarray(a, dtype=np.float64)


def bfloat16(a):
    """Round to bfloat16 and back (round to nearest even)."""
    import ml_dtypes

    return np.asarray(a, dtype=np.float64).astype(ml_dtypes.bfloat16
                                                  ).astype(np.float64)


def gamma(demands: np.ndarray, capacities: np.ndarray,
          eligibility: np.ndarray) -> np.ndarray:
    """Eq. 7: gamma[n, i] = min over demanded r of c[i, r] / d[n, r] on
    eligible pairs, else 0 (also 0 where a demanded resource is absent)."""
    d = demands[:, None, :]
    c = capacities[None, :, :]
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(d > 0, c / np.where(d > 0, d, 1.0), np.inf)
    g = ratio.min(axis=2)
    return np.where(np.isfinite(g) & (eligibility > 0), g, 0.0)


def server_fill(cap, demands, weights, gamma_i, x_ext, rounding=exact):
    """The per-server procedure on one server: raise the common normalized
    level ``L`` of its eligible users, each user ``n`` holding
    ``weights[n] * gamma_i[n] * max(0, L - floor[n])`` tasks here, where
    ``floor[n]`` is the level its tasks on other servers (``x_ext``)
    already give it. When a resource fills, every user still rising that
    demands it stops; the rest rise on until no user can. Returns the
    tasks per user on this server."""
    q = rounding
    n, r_cnt = demands.shape
    x = np.zeros(n)
    rising = gamma_i > 0
    if not rising.any():
        return x
    rate = q(weights * gamma_i)
    floor = np.where(rising, q(x_ext / np.where(rising, rate, 1.0)), np.inf)
    full = cap <= 0
    used = np.zeros(r_cnt)
    level = 0.0
    while rising.any():
        users = np.flatnonzero(rising)
        order = users[np.argsort(floor[users], kind="stable")]
        f = floor[order]
        nxt = np.append(f[1:], np.inf)
        crossing = np.full(r_cnt, np.inf)
        for r in np.flatnonzero(~full):
            slope = q(demands[order, r] * rate[order])
            cum = q(np.cumsum(slope))
            cum_f = q(np.cumsum(slope * f))
            with np.errstate(divide="ignore", invalid="ignore"):
                lvl = q((cap[r] - used[r] + cum_f) / cum)
            ok = (cum > 0) & (lvl >= f) & (lvl <= nxt)
            if ok.any():
                crossing[r] = lvl[np.argmax(ok)]
        best = crossing.min()
        if not np.isfinite(best):
            break                       # the rising users need no full resource
        best = max(best, level)
        x[order] = q(rate[order] * np.maximum(0.0, q(best - f)))
        binds = crossing <= best * (1 + 1e-12)
        stop = order[(demands[order][:, binds] > 0).any(axis=1)]
        used = q(used + demands[stop].T @ x[stop])
        full |= binds
        rising[stop] = False
        level = best
    return x


def sweep(x, demands, capacities, weights, g, rounding=exact):
    """One Gauss-Seidel pass: every server in order refilled against the
    other servers' columns as they stand, the ones before it refilled."""
    out = np.array(x, dtype=np.float64)
    totals = out.sum(axis=1)
    for i in range(out.shape[1]):
        users = np.flatnonzero(g[:, i] > 0)
        ext = totals[users] - out[users, i]
        col = np.zeros(out.shape[0])
        col[users] = server_fill(capacities[i], demands[users],
                                 weights[users], g[users, i], ext, rounding)
        totals += col - out[:, i]
        out[:, i] = col
    return out


def scale_of(g: np.ndarray) -> float:
    """The acceptance scale the program certifies against: the largest
    gamma of an active eligible pair, at least 1."""
    return max(1.0, float(g.max(initial=0.0)))


def capacity_excess(x, demands, capacities) -> float:
    """Largest use beyond capacity as a share of it (0 when feasible)."""
    use = np.einsum("nk,nr->kr", np.asarray(x, np.float64), demands)
    live = capacities > 0
    over = np.where(live, (use - capacities) / np.where(live, capacities,
                                                        1.0), use)
    return max(0.0, float(over.max()))


def saturation_gap(x, demands, capacities, g) -> float:
    """Largest distance from 1 of a server's fullest resource use over its
    capacity, over the servers with an eligible user. The per-server
    procedure stops only when every eligible user demands a full resource,
    so at a fixed point each such server has a resource used exactly to
    capacity, and damping mixes two such fills."""
    use = np.einsum("nk,nr->kr", np.asarray(x, np.float64), demands)
    live = capacities > 0
    ratio = np.where(live, use / np.where(live, capacities, 1.0), 0.0)
    busy = (g > 0).any(axis=0)
    if not busy.any():
        return 0.0
    return float(np.abs(1.0 - ratio.max(axis=1))[busy].max())


def min_vds(x, weights, g, rounding=exact) -> float:
    """Eq. 16 over all servers: the smallest x_n / (phi_n gamma[n, i]) of
    an eligible pair."""
    q = rounding
    tot = q(np.asarray(x, np.float64).sum(axis=1))
    live = g > 0
    with np.errstate(divide="ignore", invalid="ignore"):
        s = np.where(live, q(q(tot / weights)[:, None]
                             / np.where(live, g, 1.0)), np.inf)
    return float(s.min())


def vds_rel_err(reported: float, x, weights, g) -> float:
    """The program's Eq. 16 minimum against this module's, relative."""
    want = min_vds(x, weights, g)
    return abs(reported - want) / max(abs(want), np.finfo(np.float32).tiny)
