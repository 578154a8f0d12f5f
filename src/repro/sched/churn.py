"""Event-driven churn simulation over warm-started PS-DSF re-solves.

The paper's Section V experiment toggles one user on/off at two fixed times.
Datacenter reality is an event *stream*: users arrive and depart, servers
degrade and recover, and the allocator must re-equilibrate after every batch
of events. Re-solving cold after each batch wastes exactly the structure
churn preserves — the fixed point moves a little, not everywhere — so the
simulator re-solves **warm-started from the pre-event fixed point**
(``psdsf_solve_jax(x0=...)``), which empirically converges in 1-3 rounds
versus the cold solver's tens.

Events at the same timestamp are applied together and followed by one
re-solve (the "every T seconds" batching of Section III-D). Telemetry per
step includes the per-server min normalized VDS (Eq. 16) computed by the
``kernels/psdsf_vds`` reduction — the quantity a scaled scheduler would use
to rank servers for incremental re-solving.
"""
from __future__ import annotations

import dataclasses
import functools as _functools
from typing import List, Optional, Sequence

import numpy as np

from repro.core.engine import SWEEP_MECHANISMS
from repro.core.gamma import gamma_matrix
from repro.core.trace import Span, Tracer, count, span
from repro.core.types import Allocation, AllocationProblem

VALID_KINDS = ("arrival", "departure", "degrade", "restore")


@dataclasses.dataclass(frozen=True)
class ChurnEvent:
    """One state change. ``user`` for arrival/departure; ``server`` (+
    ``scale`` in (0, 1]) for degrade; ``server`` for restore."""
    time: float
    kind: str
    user: int = -1
    server: int = -1
    scale: float = 1.0

    def __post_init__(self):
        if self.kind not in VALID_KINDS:
            raise ValueError(f"unknown event kind {self.kind!r}")


@dataclasses.dataclass
class ChurnRecord:
    """Telemetry for one re-solve step."""
    time: float
    n_events: int
    rounds: int              # rounds the (warm) re-solve took
    cold_rounds: int         # rounds a cold solve would take (-1 if untracked)
    residual: float
    active_users: int
    total_tasks: float
    solve_ms: float
    min_vds: float           # global min normalized VDS over servers (Eq. 16)
    bottleneck_server: int   # server attaining it
    # lexmm router observability (zeros unless the tick flow-routed):
    lp_calls: int = 0        # LP certificates this tick
    warm_hits: int = 0       # traced stages reused via verification
    warm_fallbacks: int = 0  # loud flag: the event delta forced a full solve
    router_mode: str = ""    # "verify" / "incremental" / "fallback" / "warm"
    # fill-engine observability (mirrors SolveInfo.fill_engine/fill_iters):
    fill_engine: str = ""    # "event" / "bisect" ("" if the tick flow-routed)
    fill_iters: int = 0      # inner-iteration budget the re-solve spent
    # sparse-layout observability (mirrors SolveInfo.layout/bucket_max):
    layout: str = "dense"    # data layout the re-solve swept in
    bucket_max: int = 0      # widest eligibility bucket (0 when dense)
    layout_rebuilds: int = 0  # bucket rebuilds this step (arrivals outside
    #                           the layout rebuild loudly; departures only
    #                           mask buckets in place)
    # outer-iteration accelerator observability (mirrors SolveInfo.accel*):
    accel: str = "none"      # accelerator the re-solve swept under
    accel_hits: int = 0      # accepted Anderson candidates this step
    accel_rejects: int = 0   # safeguard fallbacks this step
    rounds_to_tol: int = 0   # rounds to the TIGHT tol (0 if not reached)
    # the step's ``churn.step`` root span (core.trace): where the step's
    # host time went, span by span, with its transfer and compile counters
    trace: Optional[Span] = dataclasses.field(default=None, repr=False,
                                              compare=False)


#: sweep-based mechanisms the simulator can maintain a fixed point for
#: (closed-form mechanisms — drf, uniform — have no per-server sweep to
#: warm); one source of truth shared with the engine's jax routing
TICKABLE_MECHANISMS = SWEEP_MECHANISMS


class ChurnSimulator:
    """Maintains an allocator fixed point through an event stream.

    ``problem`` holds the full user population; ``initial_active`` masks who
    is present at t=0 (arrivals flip users on). ``mechanism`` selects any
    sweep-based registered allocator (PS-DSF by default; the exact baselines
    re-equilibrate through the same warm-started sweep). The solver engine is
    the jitted JAX path; set ``compare_cold=True`` to also run each re-solve
    cold and record the round-count gap (used by the ``dynamic_churn``
    benchmark row). ``mode`` ("rdm"/"tdm") is the legacy PS-DSF-regime
    spelling, kept as an alias. ``placement`` selects the routing strategy
    per tick ("level", "headroom" or "lexmm"; "bestfit" is numpy-only and
    rejected): headroom re-routes via the one-shot global fill
    (global-share mechanisms; inherently cold) or repack-and-refill passes
    after the warm sweep (PS-DSF); lexmm is the identity on the PS-DSF
    level tick (already the per-server lexicographic optimum) and runs the
    exact host-side flow router per tick for the global-share mechanisms
    (one-shot exact — warm starts have nothing to speed up, and
    ``rounds`` then reports the router's freeze stages).

    ``fill`` ("event"/"bisect") and ``round`` ("gauss"/"jacobi") pick the
    per-server fill engine and outer iteration of the jitted sweep (see
    ``psdsf_jax._solve_core``); each record reports them back as
    ``fill_engine``/``fill_iters``. ``accel`` ("none"/"anderson") threads
    the safeguarded outer-iteration accelerator into every warm re-solve
    (``psdsf_jax._anderson_rounds``) — this is where it earns its keep:
    a warm start near a limit cycle finally contracts instead of
    re-orbiting — with per-step ``accel_hits``/``accel_rejects``/
    ``rounds_to_tol`` mirrored on each record.

    ``layout`` ("dense"/"bucketed"/"auto") picks the sweep's data layout
    (``core.layout``): bucketed sweeps each server's eligibility bucket —
    O(nnz) per round — with buckets built once from the ACTIVE support at
    construction. Departures mask bucket slots in place (no rebuild);
    an arrival the layout never saw rebuilds it loudly (recompile + the
    per-record ``layout_rebuilds`` flag). "auto" resolves by density of
    the initial active support.
    """

    def __init__(self, problem: AllocationProblem, mode: Optional[str] = None,
                 warm_start: bool = True, compare_cold: bool = False,
                 max_rounds: int = 256, tol: float = 1e-6,
                 initial_active: Optional[np.ndarray] = None,
                 telemetry: bool = True,
                 mechanism: Optional[str] = None, placement: str = "level",
                 fill: str = "event", round: str = "gauss",
                 layout: str = "auto", accel: str = "none"):
        import jax.numpy as jnp

        from repro.core.layout import LAYOUTS, resolve_layout
        from repro.core.placement import (ACCEL_ENGINES, FILL_ENGINES,
                                          get_placement)

        if mode is not None and mechanism is not None:
            raise ValueError(
                "pass either the legacy mode= alias or mechanism=, not both")
        if mode is not None:
            if mode not in ("rdm", "tdm"):
                raise ValueError(f"mode must be 'rdm' or 'tdm': {mode!r}")
            mechanism = f"psdsf-{mode}"
        if mechanism is None:
            mechanism = "psdsf-rdm"
        if mechanism not in TICKABLE_MECHANISMS:
            raise ValueError(
                f"mechanism must be sweep-based, one of "
                f"{TICKABLE_MECHANISMS}: {mechanism!r}")
        if not get_placement(placement).jax_backend:
            raise ValueError(
                f"the churn tick runs on the jitted engine; placement "
                f"{placement!r} has no jitted mirror (numpy only)")
        if fill not in FILL_ENGINES:
            raise ValueError(f"fill must be one of {FILL_ENGINES}: {fill!r}")
        if round not in ("gauss", "jacobi"):
            raise ValueError(f"round must be 'gauss' or 'jacobi': {round!r}")
        if accel not in ACCEL_ENGINES:
            raise ValueError(f"accel must be one of {ACCEL_ENGINES}: "
                             f"{accel!r}")
        self.problem = problem
        self.mechanism = mechanism
        self.placement = placement
        self.fill = fill
        self.round = round
        self.accel = accel
        self.warm_start = warm_start
        self.compare_cold = compare_cold
        self.max_rounds = max_rounds
        self.tol = tol
        self.telemetry = telemetry
        n, k = problem.num_users, problem.num_servers
        self.active = (np.ones(n, dtype=bool) if initial_active is None
                       else np.asarray(initial_active, dtype=bool).copy())
        self.cap_scale = np.ones(k)
        self.x = np.zeros((n, k))
        self._resolve = _resolve_fn()
        # buckets are built from the ACTIVE support at construction time:
        # departures only mask bucket slots in place, arrivals of users the
        # layout never saw rebuild it (loudly — counted per record)
        routed = (placement == "headroom"
                  and mechanism not in ("psdsf-rdm", "psdsf-tdm"))
        if layout not in LAYOUTS:
            raise ValueError(f"layout must be one of {LAYOUTS}: {layout!r}")
        if routed and layout == "bucketed":
            raise ValueError(
                "layout='bucketed' needs the per-server sweep; the routed "
                "headroom fill for global-share mechanisms is one-shot "
                "global — use layout='dense'")
        self._blayout = None
        self.layout_rebuilds = 0
        self._needs_rebuild = False
        with Tracer("churn.init"):
            self._demands = jnp.asarray(problem.demands, jnp.float32)
            self._caps = jnp.asarray(problem.capacities, jnp.float32)
            self._weights = jnp.asarray(problem.weights, jnp.float32)
            self._elig = jnp.asarray(problem.eligibility, jnp.float32)
            count("h2d_bytes", sum(a.nbytes for a in (
                self._demands, self._caps, self._weights, self._elig)))
            self.layout = ("dense" if routed else resolve_layout(
                layout, support=(problem.eligibility > 0)
                & self.active[:, None]))
            if self.layout == "bucketed":
                self._build_buckets()
        # persistent lexmm router (global-share + placement="lexmm" ticks):
        # built lazily on the BASE capacities; degrade/restore re-scale its
        # rhs in place, arrivals/departures flow in as activity deltas
        self._lexmm_router = None
        self._router_stats = None

    def _build_buckets(self) -> None:
        import jax.numpy as jnp

        from repro.core.layout import BucketedLayout

        supp = (self.problem.eligibility > 0) & self.active[:, None]
        self._blayout = BucketedLayout.from_support(supp)
        self._covered = self.active.copy()     # users the layout has slots for
        self._idx_j = jnp.asarray(self._blayout.indices)
        self._mask_j = jnp.asarray(self._blayout.mask)
        count("h2d_bytes", self._idx_j.nbytes + self._mask_j.nbytes)
        self._needs_rebuild = False

    # -- event application --------------------------------------------------
    def _apply(self, ev: ChurnEvent) -> None:
        if ev.kind == "arrival":
            self.active[ev.user] = True
            if self._blayout is not None and not self._covered[ev.user]:
                self._needs_rebuild = True
        elif ev.kind == "departure":
            self.active[ev.user] = False
            self.x[ev.user, :] = 0.0
        elif ev.kind == "degrade":
            if not 0.0 < ev.scale <= 1.0:
                raise ValueError(f"degrade scale must be in (0, 1]: {ev.scale}")
            self.cap_scale[ev.server] = ev.scale
        elif ev.kind == "restore":
            self.cap_scale[ev.server] = 1.0

    def _solve(self, x0) -> tuple[np.ndarray, int, float, int, int]:
        import jax
        import jax.numpy as jnp
        if (self.placement == "lexmm"
                and self.mechanism not in ("psdsf-rdm", "psdsf-tdm")):
            return self._solve_lexmm_host()
        with span("churn.solve.upload"):
            up = (jnp.asarray(self.active),
                  jnp.asarray(self.cap_scale, jnp.float32),
                  None if x0 is None else jnp.asarray(x0, jnp.float32))
            count("h2d_bytes", sum(a.nbytes for a in up if a is not None))
        # the host blocks here rather than in the download below, so the
        # download span times the copy alone
        with span("churn.solve.wait"):
            out = jax.block_until_ready(self._resolve(
                self._demands, self._caps, self._weights, self._elig, *up,
                mechanism=self.mechanism, max_rounds=self.max_rounds,
                tol=self.tol, placement=self.placement, fill=self.fill,
                round=self.round, layout=self.layout,
                buckets=(None if self._blayout is None
                         else (self._idx_j, self._mask_j)),
                accel=self.accel))
        with span("churn.solve.download"):
            n_out = 5 if self.accel == "anderson" else 3
            x = np.array(out[0], dtype=np.float64)
            rounds, resid = int(out[1]), float(out[2])
            hits, rejects = ((int(out[3]), int(out[4]))
                             if n_out == 5 else (0, 0))
            count("d2h_bytes", sum(a.nbytes for a in out[:n_out]))
        return x, rounds, resid, hits, rejects

    def _solve_lexmm_host(self) -> tuple[np.ndarray, int, float, int, int]:
        """Exact flow-routed re-solve for the global-share mechanisms: the
        lexmm certificates are host-side LP solves (no XLA mirror), so the
        tick hands the event delta to a persistent ``RouterState`` instead
        of re-solving from scratch — departures re-verify the cached stage
        trace and re-solve only the unfrozen suffix, unchanged ticks verify
        every stage with one LP each, and arrivals or capacity changes
        trigger a (matrix-warm) full solve flagged via
        ``ChurnRecord.warm_fallbacks``. Every path is re-proven against the
        current network, so the allocation matches a from-scratch solve to
        LP round-off."""
        from repro.core.baselines import level_rate_matrix
        from repro.core.flowrouter import RouterState

        lg = level_rate_matrix(self._effective_problem(), self.mechanism)
        router = self._lexmm_router
        if router is not None:
            try:
                router.update(level_gamma=lg, capacity_scale=self.cap_scale)
            except ValueError:       # eligibility support changed: rebuild
                router = None
        if router is None:
            # build on the BASE capacities so degrade/restore compose as
            # pure rhs re-scales against a fixed normalization
            base_lg = level_rate_matrix(self.problem, self.mechanism)
            router = RouterState(self.problem, base_lg)
            router.update(level_gamma=lg, capacity_scale=self.cap_scale)
            self._lexmm_router = router
        x, stats = router.resolve(active=self.active)
        self._router_stats = stats
        return x, stats.stages, 0.0, 0, 0

    def step(self, events: Sequence[ChurnEvent], time_now: float
             ) -> ChurnRecord:
        """Apply simultaneous events, re-solve, record telemetry. The
        step runs under a ``churn.step`` root span (``core.trace``), which
        the record carries as ``trace``."""
        with Tracer("churn.step") as tr:
            return self._step(events, time_now, tr.top)

    def _step(self, events: Sequence[ChurnEvent], time_now: float,
              trace: Span) -> ChurnRecord:
        with span("churn.apply"):
            for ev in events:
                self._apply(ev)
            rebuilds = 0
            if self._needs_rebuild:
                # an arrival outside the layout: rebuild from the new active
                # support (a new Bmax recompiles the jitted sweep — loud by
                # design, and counted so streams can budget for it)
                with span("churn.rebuild"):
                    self._build_buckets()
                self.layout_rebuilds += 1
                rebuilds = 1
        self._router_stats = None
        with span("churn.solve") as solve_span:
            x, rounds, resid, hits, rejects = self._solve(
                self.x if self.warm_start else None)
        rs = self._router_stats          # lexmm ticks only, else None
        cold_rounds = -1
        if self.compare_cold and self.warm_start:
            with span("churn.cold"):
                _, cold_rounds, *_ = self._solve(None)
        self.x = x
        mn, arg = (self._min_vds() if self.telemetry else (np.inf, -1))
        from repro.core.placement import fill_iter_budget

        psdsf = self.mechanism in ("psdsf-rdm", "psdsf-tdm")
        swept = rs is None and (psdsf or self.placement != "headroom")
        budget = (rounds * self.problem.num_servers * fill_iter_budget(
            self.problem.num_resources,
            "tdm" if self.mechanism == "psdsf-tdm" else "rdm", self.fill)
            if swept else 0)
        # tight-tol certification against the same active-gamma scale the
        # traced sweep accepts on (routed/lexmm ticks are one-shot exact)
        if swept:
            with span("churn.certify"):
                g_act = np.where(self.active[:, None],
                                 gamma_matrix(self._effective_problem()), 0.0)
                tight = resid <= self.tol * float(g_act.max(initial=1.0))
        else:
            tight = resid == 0.0
        return ChurnRecord(
            time=time_now, n_events=len(events), rounds=rounds,
            cold_rounds=cold_rounds, residual=resid,
            active_users=int(self.active.sum()),
            total_tasks=float(self.x.sum()), solve_ms=solve_span.ms,
            min_vds=float(mn), bottleneck_server=int(arg),
            lp_calls=0 if rs is None else rs.lp_calls,
            warm_hits=0 if rs is None else rs.warm_hits,
            warm_fallbacks=0 if rs is None else rs.warm_fallbacks,
            router_mode="" if rs is None else rs.mode,
            fill_engine=self.fill if swept else "",
            fill_iters=budget,
            layout=self.layout if swept else "dense",
            bucket_max=(self._blayout.bucket_max if swept
                        and self._blayout is not None else 0),
            layout_rebuilds=rebuilds,
            accel=self.accel if swept else "none",
            accel_hits=hits, accel_rejects=rejects,
            rounds_to_tol=rounds if tight else 0, trace=trace)

    def run(self, events: Sequence[ChurnEvent]) -> List[ChurnRecord]:
        """Consume a whole stream: batch same-timestamp events, one re-solve
        per batch (events must be time-sorted)."""
        records = []
        i, evs = 0, sorted(events, key=lambda e: e.time)
        while i < len(evs):
            j = i
            while j < len(evs) and evs[j].time == evs[i].time:
                j += 1
            records.append(self.step(evs[i:j], evs[i].time))
            i = j
        return records

    # -- telemetry ----------------------------------------------------------
    def _min_vds(self) -> tuple[float, int]:
        from repro.core.dynamic import min_vds_guarded

        with span("churn.telemetry"):
            with span("churn.telemetry.gamma"):
                g = gamma_matrix(self._effective_problem())
            mn, _ = min_vds_guarded(self.x, self.problem.weights, g,
                                     self.active)
            i = int(np.argmin(mn))
            return float(mn[i]), i

    def _effective_problem(self) -> AllocationProblem:
        return AllocationProblem(
            self.problem.demands,
            self.problem.capacities * self.cap_scale[:, None],
            self.problem.weights, self.problem.eligibility)

    def allocation(self) -> Allocation:
        """Current allocation against the degrade-scaled capacities."""
        return Allocation(self._effective_problem(), self.x.copy())


@_functools.lru_cache(maxsize=1)
def _resolve_fn():
    """Jitted: effective capacities -> level-rate matrix for the chosen
    mechanism -> warm-started sweep (or the routed/repacked placement
    mirrors when ``placement="headroom"``). Cached so all simulator
    instances share one jit cache (one compilation per (mechanism,
    placement, shapes))."""
    import functools

    import jax.numpy as jnp
    import jax

    from repro.core.baselines_jax import (_routed_fill_core,
                                          level_rate_matrix_jnp)
    from repro.core.psdsf_jax import (_check_accel, _repack_refill_core,
                                      _solve_core, _solve_core_bucketed,
                                      gamma_matrix_jnp)

    @functools.partial(jax.jit, static_argnames=("mechanism", "max_rounds",
                                                 "placement", "fill",
                                                 "round", "layout", "accel"))
    def resolve(demands, capacities, weights, eligibility, active, cap_scale,
                x0, *, mechanism, max_rounds, tol, placement="level",
                fill="event", round="gauss", layout="dense", buckets=None,
                accel="none"):
        _check_accel(accel)
        caps_eff = capacities * cap_scale[:, None]
        g = gamma_matrix_jnp(demands, caps_eff, eligibility)
        g = jnp.where(active[:, None], g, 0.0)
        psdsf = mechanism in ("psdsf-rdm", "psdsf-tdm")
        if psdsf:
            lg = g
            mode = mechanism.removeprefix("psdsf-")
        else:
            lg = level_rate_matrix_jnp(demands, caps_eff, eligibility,
                                       mechanism)
            lg = jnp.where(active[:, None], lg, 0.0)
            mode = "rdm"
        if placement == "lexmm" and not psdsf:
            # guarded in ChurnSimulator._solve (host-side flow router);
            # reaching the trace means a caller bypassed it
            raise ValueError("lexmm for global-share mechanisms solves "
                             "host-side, not in the jitted resolve")
        if placement == "headroom" and not psdsf:
            # global-share mechanisms route via the one-shot exact fill;
            # there is no fixed point to warm-start
            if layout == "bucketed":
                raise ValueError("routed headroom fill has no bucketed "
                                 "form; guarded in ChurnSimulator.__init__")
            out = _routed_fill_core(demands, caps_eff, weights, lg)
            if accel == "anderson":  # one-shot fill: nothing to accelerate
                zero = jnp.asarray(0, jnp.int32)
                out = out + (zero, zero)
            return out
        if x0 is None:
            x0 = jnp.zeros(lg.shape, dtype=demands.dtype)
        x0 = jnp.where(active[:, None], x0, 0.0)
        # acceptance band always on the ACTIVE users' per-server gamma scale
        # (the baseline level rates sum gamma over servers — see
        # baselines_jax; and a departed huge-gamma user must not loosen it)
        if layout == "bucketed":
            # departure-only churn masks bucket slots in place: the layout
            # was built from the active support, so departed users' slots
            # exist and simply go dark under the activity mask
            idx, mask = buckets
            out = _solve_core_bucketed(demands, caps_eff, weights, lg, x0,
                                       idx, mask & active[idx], mode,
                                       max_rounds, tol, scale=g.max(),
                                       fill=fill, round_mode=round,
                                       accel=accel)
        else:
            out = _solve_core(demands, caps_eff, weights, lg, x0, mode,
                              max_rounds, tol, scale=g.max(), fill=fill,
                              round_mode=round, accel=accel)
        if placement == "headroom":
            fixed = _repack_refill_core(demands, caps_eff, weights, g,
                                        *out[:3], mode, max_rounds, tol,
                                        fill=fill, round_mode=round)
            out = fixed + out[3:]
        return out

    return resolve


def poisson_churn_events(n_users: int, n_servers: int, horizon: float,
                         arrival_rate: float = 0.5,
                         departure_rate: float = 0.5,
                         degrade_rate: float = 0.05,
                         seed: int = 0) -> List[ChurnEvent]:
    """Random event stream on integer timestamps (the scheduler's T-second
    grid): per tick, Poisson-many departures/arrivals of random users plus
    occasional server degrades/restores."""
    rng = np.random.default_rng(seed)
    present = np.ones(n_users, dtype=bool)
    degraded: dict[int, bool] = {}
    events: List[ChurnEvent] = []
    for t in range(1, int(horizon) + 1):
        for _ in range(rng.poisson(departure_rate)):
            on = np.nonzero(present)[0]
            if on.size > 1:                      # keep >= 1 user active
                u = int(rng.choice(on))
                present[u] = False
                events.append(ChurnEvent(float(t), "departure", user=u))
        for _ in range(rng.poisson(arrival_rate)):
            off = np.nonzero(~present)[0]
            if off.size:
                u = int(rng.choice(off))
                present[u] = True
                events.append(ChurnEvent(float(t), "arrival", user=u))
        if rng.random() < degrade_rate:
            s = int(rng.integers(n_servers))
            if degraded.get(s):
                degraded[s] = False
                events.append(ChurnEvent(float(t), "restore", server=s))
            else:
                degraded[s] = True
                events.append(ChurnEvent(
                    float(t), "degrade", server=s,
                    scale=float(rng.uniform(0.3, 0.8))))
    return events
