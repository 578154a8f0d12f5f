"""Distributed / asynchronous PS-DSF (Section III-D and the Section V
experiment).

Each server executes the *server procedure* independently every T seconds
using only (a) its local capacities and (b) the global task counts x_n.
``DistributedPSDSF`` models this: ``tick(servers)`` rebuilds the chosen
servers' allocations (all servers = one synchronous round; subsets/permuted
orders = asynchronous execution). User churn (arrivals/departures) is
supported by an activity mask — exactly the Section V experiment where user 4
is inactive during (100, 250) s.

Two engines:

* ``engine="numpy"`` — the reference oracle: a pure-Python loop over
  ``psdsf.server_fill_*`` per server. Exact (float64), easy to read, slow.
* ``engine="jax"`` — the jitted sweep core's Gauss-Seidel round
  (``psdsf_jax._gauss_round``) over the selected servers, as a (len, 1)
  sweep: one server a step, in the listed order, never the colour classes
  the jitted solvers fill at once. Identical Gauss-Seidel order and math,
  so the engines agree to fp32 round-off; this is what makes 10^3-server
  ticks at scheduler rates feasible.

``min_vds()`` exposes the per-server normalized-VDS reduction (Eq. 16) via
the ``kernels/psdsf_vds`` Pallas op — the scheduler-telemetry hot loop that
the churn simulator uses to rank servers for re-solving.
"""
from __future__ import annotations

import functools
from typing import Iterable, Optional, Sequence

import numpy as np

from .gamma import gamma_matrix
from .psdsf import (server_fill_rdm, server_fill_rdm_bisect, server_fill_tdm,
                    server_fill_tdm_bisect)
from .trace import span
from .types import Allocation, AllocationProblem

_ENGINES = ("numpy", "jax")


@functools.lru_cache(maxsize=1)
def _tick_jax_fn():
    """Build the jitted tick lazily so importing this module never pulls in
    jax for numpy-engine users; cached so every engine instance shares one
    jit cache instead of recompiling per instance.

    The tick is the sweep core's Gauss-Seidel round
    (``psdsf_jax._gauss_round``) at alpha 1 over ``servers``, one server a
    step in the listed order (a (len, 1) sweep: the numpy oracle's order),
    with gamma zeroed for inactive users. ``buckets=(idx, mask)`` (a
    ``layout.BucketedLayout``'s arrays) fills each server over its
    eligibility bucket — O(nnz) per full tick; ``None`` is the full layout.
    Either way the dense state round-trips through the bucket
    gather/scatter exactly (allocations live only on the support)."""
    import jax
    import jax.numpy as jnp

    from .psdsf_jax import (_bucket_fill, _full_layout, _gather,
                            _gauss_round, _scatter)

    @functools.partial(jax.jit, static_argnames=("mode", "fill"))
    def tick(x, demands, capacities, weights, gamma, active, servers,
             buckets=None, *, mode, fill="event"):
        n, k = x.shape
        idx, mask = _full_layout(n, k) if buckets is None else buckets
        gamma = jnp.where(active[:, None], gamma, 0.0)
        fill_server = _bucket_fill(mode, fill, demands, capacities, weights,
                                   gamma, idx, mask)
        xb, _ = _gauss_round(fill_server, idx, mask, servers[:, None], n,
                             _gather(x, idx, mask), 1.0)
        return _scatter(xb, idx, mask, n)

    return tick


def min_vds_guarded(x: np.ndarray, weights: np.ndarray, gamma: np.ndarray,
                    active: np.ndarray):
    """The Eq. 16 reduction with the inactive/zero-weight mask applied
    BEFORE the division: a zero-weight user (weights are validated > 0 at
    construction, but callers can rescale the array in place) must be
    excluded exactly like an inactive one, not turn a server's min into
    inf/NaN. Used by ``DistributedPSDSF.min_vds`` on its host gamma (the
    churn simulator builds the same inputs on the device instead,
    ``ChurnSimulator._min_vds``).

    The backend decides how the Pallas kernel runs
    (``psdsf_vds.ops._vds_interpret``)."""
    from repro.kernels.psdsf_vds.ops import _vds_interpret, min_vds_padded

    interpret = _vds_interpret()
    with span("vds.prep"):
        mask = np.asarray(active, dtype=bool) & (weights > 0)
        with np.errstate(divide="ignore", invalid="ignore"):
            x_over_phi = np.where(mask, x.sum(axis=1)
                                  / np.where(mask, weights, 1.0), 0.0)
        gamma = np.where(mask[:, None], gamma, 0.0)
    return min_vds_padded(x_over_phi, gamma, interpret=interpret)


class DistributedPSDSF:
    """``placement`` mirrors the strategy axis of the batch solvers at the
    asynchronous tick layer: ``level`` (default) and ``lexmm`` tick
    unchanged — the per-server fill IS the level placement, and PS-DSF's
    per-server water levels are already the per-server lexicographic
    optimum — while ``headroom``/``bestfit`` follow every tick with one
    totals-preserving ``placement.repack_pass`` (proportional / greedy),
    the asynchronous analogue of ``repack_refill`` (feasibility is
    preserved by construction; the next tick re-equilibrates the levels).

    ``fill`` selects the per-server fill engine on both backends:
    ``"event"`` (argsort + saturation-event scan) or ``"bisect"`` (the
    sort-free monotone-bisection engine — identical fixed point, see
    ``placement.server_fill_rdm_bisect``).

    ``layout`` selects the sweep's data layout on both backends:
    ``"dense"`` fills every server against all N users, ``"bucketed"``
    pre-gathers each server's eligibility bucket (``core.layout``) so a
    tick costs O(nnz) instead of O(N*K) — identical allocations (users
    outside a bucket have gamma 0 and always fill to zero); ``"auto"``
    (default) picks by support density. Resolved layout and bucket size
    are exposed as ``self.layout`` / ``self.bucket_max``.

    ``accel`` mirrors the batch solvers' outer-iteration axis at the tick
    layer: ``"anderson"`` runs host-side safeguarded Anderson mixing ACROSS
    consecutive synchronous full ticks (``tick()`` with no server subset and
    no shuffle) — each mixed candidate is certified by a second full tick
    and accepted only if it shrinks the tick residual, so state after
    ``tick()`` is always the output of a genuine server-procedure round.
    Partial/shuffled ticks and ``set_active`` churn restart the mixing
    history (the map being accelerated changed); accepted/rejected
    candidates are counted on ``self.accel_hits`` / ``self.accel_rejects``.
    """

    def __init__(self, problem: AllocationProblem, mode: str = "rdm",
                 seed: int = 0, engine: str = "numpy",
                 precision: str = "highest", placement: str = "level",
                 fill: str = "event", layout: str = "auto",
                 accel: str = "none"):
        from .layout import BucketedLayout, resolve_layout
        from .placement import ACCEL_ENGINES, FILL_ENGINES, get_placement

        if mode not in ("rdm", "tdm"):
            raise ValueError(f"mode must be 'rdm' or 'tdm': {mode!r}")
        if engine not in _ENGINES:
            raise ValueError(f"engine must be one of {_ENGINES}: {engine}")
        if precision not in ("highest", "fast"):
            raise ValueError(
                f"precision must be 'highest' or 'fast': {precision!r}")
        if fill not in FILL_ENGINES:
            raise ValueError(f"fill must be one of {FILL_ENGINES}: {fill}")
        if accel not in ACCEL_ENGINES:
            raise ValueError(f"accel must be one of {ACCEL_ENGINES}: "
                             f"{accel!r}")
        get_placement(placement)               # unknown strategies fail fast
        self.problem = problem
        self.mode = mode
        self.engine = engine
        self.fill = fill
        self.placement = placement
        self.accel = accel
        self.accel_hits = 0
        self.accel_rejects = 0
        self._hist_f: list = []      # tick-to-tick Anderson history
        self._hist_g: list = []
        self.gamma = gamma_matrix(problem)
        self.layout = resolve_layout(layout, support=self.gamma)
        self.x = np.zeros((problem.num_users, problem.num_servers))
        self.active = np.ones(problem.num_users, dtype=bool)
        self._rng = np.random.default_rng(seed)
        self._router = None          # persistent lexmm router (comparator)
        self._router_mech: Optional[str] = None
        self.router_stats = None     # RouterStats of the last routed call
        self._blayout = None
        if self.layout == "bucketed":
            self._blayout = BucketedLayout.from_support(self.gamma > 0)
            self._buckets = self._blayout.bucket_lists()
            self._dem_b = [problem.demands[u] for u in self._buckets]
            self._phi_b = [problem.weights[u] for u in self._buckets]
            self._gam_b = [self.gamma[u, i]
                           for i, u in enumerate(self._buckets)]
        self.bucket_max = (0 if self._blayout is None
                           else self._blayout.bucket_max)
        if engine == "jax":
            import jax.numpy as jnp
            # "highest" ticks in f64 (bit-comparable to the numpy oracle even
            # when x_n sums span 10^3 servers); "fast" in f32 (accelerators).
            self._x64 = precision == "highest"
            dt = jnp.float64 if self._x64 else jnp.float32
            with self._precision_scope():
                self._tick_jax = _tick_jax_fn()
                self._demands = jnp.asarray(problem.demands, dt)
                self._caps = jnp.asarray(problem.capacities, dt)
                self._weights = jnp.asarray(problem.weights, dt)
                self._gamma = jnp.asarray(self.gamma, dt)
                self._buckets_j = (
                    None if self._blayout is None
                    else (jnp.asarray(self._blayout.indices),
                          jnp.asarray(self._blayout.mask)))

    def _precision_scope(self):
        import contextlib

        import jax
        return (jax.enable_x64(True) if self._x64
                else contextlib.nullcontext())

    # -- churn -------------------------------------------------------------
    def set_active(self, user: int, active: bool) -> None:
        """Arrival/departure: departures also release the user's tasks.
        Churn changes the tick map, so the Anderson history restarts."""
        self.active[user] = active
        if not active:
            self.x[user, :] = 0.0      # departing user releases its tasks
        self._hist_f = []
        self._hist_g = []

    # -- the per-server procedure -------------------------------------------
    def tick(self, servers: Optional[Iterable[int]] = None,
             shuffle: bool = False) -> None:
        """One asynchronous round of Algorithm 1: each listed server (all
        by default) runs its local PS-DSF procedure against current state.

        Under ``accel="anderson"`` a synchronous full tick additionally
        mixes the tick-to-tick history (safeguarded by a second full tick,
        see the class docstring); partial or shuffled visits tick plainly
        and restart the history."""
        p = self.problem
        full = servers is None and not shuffle
        idx: Sequence[int] = list(range(p.num_servers) if servers is None
                                  else servers)
        if shuffle:
            self._rng.shuffle(idx)
        if self.accel == "anderson" and full:
            self._tick_anderson(idx)
        else:
            if self.accel == "anderson":
                # the mixing history models the synchronous full-tick map;
                # an asynchronous visit changes that map — restart
                self._hist_f = []
                self._hist_g = []
            self._tick_once(idx)
        self._repack_if_routed()

    def _tick_once(self, idx: Sequence[int]) -> None:
        """One plain visit sequence (no repack, no mixing) — the map the
        Anderson layer accelerates and the safeguard certifies with."""
        p = self.problem
        if self.engine == "jax":
            self._tick_with_jax(np.asarray(list(idx), dtype=np.int32))
            return
        # Row sums feeding the external floors are maintained incrementally:
        # one O(NK) reduction per tick, O(N) updates per server after that.
        bisect = self.fill == "bisect"
        xsum = self.x.sum(axis=1)
        if self._blayout is not None:
            # bucketed: each server fills its pre-gathered eligibility
            # bucket only — O(bucket) per server, O(nnz) per full tick
            for i in idx:
                u = self._buckets[i]
                if u.size == 0:
                    continue
                gamma_i = np.where(self.active[u], self._gam_b[i], 0.0)
                x_ext = xsum[u] - self.x[u, i]
                if self.mode == "rdm":
                    f = server_fill_rdm_bisect if bisect else server_fill_rdm
                    xi = f(p.capacities[i], self._dem_b[i], self._phi_b[i],
                           gamma_i, x_ext)
                else:
                    f = server_fill_tdm_bisect if bisect else server_fill_tdm
                    xi = f(self._dem_b[i], self._phi_b[i], gamma_i, x_ext)
                xsum[u] += xi - self.x[u, i]
                self.x[u, i] = xi
            return
        for i in idx:
            gamma_i = np.where(self.active, self.gamma[:, i], 0.0)
            x_ext = xsum - self.x[:, i]
            if self.mode == "rdm":
                f = server_fill_rdm_bisect if bisect else server_fill_rdm
                xi = f(p.capacities[i], p.demands, p.weights, gamma_i, x_ext)
            else:
                f = server_fill_tdm_bisect if bisect else server_fill_tdm
                xi = f(p.demands, p.weights, gamma_i, x_ext)
            xsum += xi - self.x[:, i]
            self.x[:, i] = xi

    def _tick_anderson(self, idx: Sequence[int]) -> None:
        """Host-side safeguarded Anderson mixing across full ticks — the
        asynchronous analogue of ``placement._anderson_fixed_point``. One
        plain tick always runs first; a mixed candidate (numpy lstsq over
        the tick-to-tick difference history) is evaluated by a SECOND full
        tick and kept only if that tick's residual beats the plain one, so
        ``self.x`` always ends on the output of a real server-procedure
        round and a rejected candidate costs progress, never exactness."""
        from .placement import ANDERSON_MEMORY

        x_prev = self.x.copy()
        self._tick_once(idx)
        g = self.x.copy()
        resid = float(np.abs(g - x_prev).max())
        f = (g - x_prev).ravel()
        self._hist_f.append(f)
        self._hist_g.append(g.ravel())
        if len(self._hist_f) > ANDERSON_MEMORY + 1:
            self._hist_f.pop(0)
            self._hist_g.pop(0)
        if len(self._hist_f) < 2 or resid == 0.0:
            return
        hf, hg = self._hist_f, self._hist_g
        df = np.stack([hf[j + 1] - hf[j] for j in range(len(hf) - 1)], axis=1)
        dg = np.stack([hg[j + 1] - hg[j] for j in range(len(hg) - 1)], axis=1)
        theta, *_ = np.linalg.lstsq(df, f, rcond=None)
        cand = np.maximum(hg[-1] - dg @ theta, 0.0).reshape(self.x.shape)
        self.x = cand.copy()
        self._tick_once(idx)                 # safeguard evaluation tick
        g_c = self.x.copy()
        resid_c = float(np.abs(g_c - cand).max())
        if np.isfinite(resid_c) and resid_c < resid:
            self.accel_hits += 1
            self._hist_f.append((g_c - cand).ravel())
            self._hist_g.append(g_c.ravel())
            if len(self._hist_f) > ANDERSON_MEMORY + 1:
                self._hist_f.pop(0)
                self._hist_g.pop(0)
        else:
            self.accel_rejects += 1
            self.x = g                       # fall back to the plain tick
            self._hist_f = [f]
            self._hist_g = [g.ravel()]

    def _repack_if_routed(self) -> None:
        """headroom/bestfit: one totals-preserving repack per tick (see the
        class docstring); level/lexmm tick untouched."""
        if self.placement not in ("headroom", "bestfit"):
            return
        from .placement import repack_pass

        g = np.where(self.active[:, None], self.gamma, 0.0)
        self.x = repack_pass(self.problem, self.x, g, mode=self.mode,
                             greedy=self.placement == "bestfit")

    def _tick_with_jax(self, servers: np.ndarray) -> None:
        import jax.numpy as jnp
        with self._precision_scope():
            x = self._tick_jax(
                jnp.asarray(self.x, self._demands.dtype), self._demands,
                self._caps, self._weights, self._gamma,
                jnp.asarray(self.active), jnp.asarray(servers),
                self._buckets_j, mode=self.mode, fill=self.fill)
            x.block_until_ready()
        self.x = np.array(x, dtype=np.float64)   # copy: keep self.x writable

    # -- exact routed comparator ---------------------------------------------
    def routed_allocation(self, mechanism: str = "tsf") -> Allocation:
        """Exact lexmm-routed allocation of a *global-share* mechanism under
        the current activity mask.

        PS-DSF's own tick needs no flow router (the per-server fill IS the
        per-server lexicographic optimum), but the Section V comparisons
        read a global-share quota next to it. This keeps one persistent warm
        ``flowrouter.RouterState`` per mechanism and hands it the
        ``set_active`` churn as an activity delta — an unchanged mask
        re-verifies the cached stage trace (one LP per stage), departures
        re-solve only the unfrozen suffix, arrivals fall back to a full
        matrix-warm solve flagged in ``self.router_stats.warm_fallbacks``.
        """
        from repro.core.baselines import level_rate_matrix

        from .flowrouter import RouterState

        if self._router is None or self._router_mech != mechanism:
            lg = level_rate_matrix(self.problem, mechanism)
            self._router = RouterState(self.problem, lg)
            self._router_mech = mechanism
        x, stats = self._router.resolve(active=self.active)
        self.router_stats = stats
        return Allocation(self.problem, x)

    # -- telemetry ----------------------------------------------------------
    def min_vds(self):
        """Per-server (min normalized VDS, argmin user) over active users —
        Eq. 16 via the Pallas ``psdsf_vds`` reduction, compiled on TPU and
        interpreted on CPU (see ``min_vds_guarded``).

        Servers where no active user is eligible report BIG (~3e38); that
        includes the all-inactive edge case. Users whose weight has been
        zeroed (in-place, after problem validation) are excluded like
        inactive users — an unguarded ``x_n / phi_n`` would otherwise
        poison the server min with inf/NaN.
        """
        return min_vds_guarded(self.x, self.problem.weights, self.gamma,
                                self.active)

    def allocation(self) -> Allocation:
        """Snapshot of the current state as an :class:`Allocation`."""
        return Allocation(self.problem, self.x.copy())

    def utilization(self) -> np.ndarray:
        """(K, R) resource utilization of the current state."""
        return self.allocation().utilization()
