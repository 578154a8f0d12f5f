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
from typing import Any, List, NamedTuple, Optional, Sequence

import numpy as np

from repro.core.engine import SWEEP_MECHANISMS
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


class _Resolved(NamedTuple):
    """What one jitted resolve left on the device, and its certificate
    scale (read back with its scalars)."""
    active: Any              # (N,) bool, as uploaded
    cap_scale: Any           # (K,) float32, as uploaded
    x: Any                   # (N, K) float32 allocation, on the device
    scale: float             # max(1, largest active gamma)
    total: float             # x.sum()


class _OnDevice(NamedTuple):
    """An allocation the jitted resolve left on the device, (N, K)
    float32. ``copy()`` downloads it as a writable float64 host array, the
    form a host-solved tick's answer has."""
    array: Any

    def copy(self) -> np.ndarray:
        """The allocation downloaded, as a new float64 host array."""
        return np.array(self.array, dtype=np.float64)


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
    construction; dense passes no buckets, and the one jitted sweep core
    runs the full layout (every server holds every user). Departures mask bucket slots in place (no rebuild);
    an arrival the layout never saw rebuilds it loudly (recompile + the
    per-record ``layout_rebuilds`` flag). "auto" resolves by density of
    the initial active support.

    The allocation stays on the device between steps, as the float32
    array the jitted resolve returned: the next warm start is that array,
    a departure zeroes its row inside the resolve, and ``total_tasks`` is
    summed there. ``x`` downloads it (as float64) on its first read after
    a step; ``x_reads`` counts those downloads. A global-share lexmm tick
    solves on the host and keeps its answer there.
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
        self._departed = np.zeros(n, dtype=bool)  # since the last upload
        self._x_dev = jnp.zeros((n, k), jnp.float32)
        self._x_host: Optional[np.ndarray] = np.zeros((n, k))
        self.x_reads = 0
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
        self._resolved: Optional[_Resolved] = None

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
            # the resolve zeroes the row of the warm start, even if the user
            # arrives again in this batch; ``x`` shows it zeroed already
            self.active[ev.user] = False
            self._departed[ev.user] = True
            if self._x_host is not None:
                self._x_host[ev.user] = 0.0
        elif ev.kind == "degrade":
            if not 0.0 < ev.scale <= 1.0:
                raise ValueError(f"degrade scale must be in (0, 1]: {ev.scale}")
            self.cap_scale[ev.server] = ev.scale
        elif ev.kind == "restore":
            self.cap_scale[ev.server] = 1.0

    def _solve(self, x0) -> tuple[Any, int, float, int, int]:
        """Re-solve from ``x0``, the device allocation (None: cold).
        Returns the allocation, as ``_OnDevice`` (a host array from a lexmm
        tick), with rounds, residual and Anderson hits and rejects. The
        jitted resolve also leaves what it holds on the device in
        ``self._resolved``: the uploaded activity and degrade scales, the
        allocation, and the certificate's scale and the allocation's sum,
        read back with the other scalars."""
        import jax
        departed, self._departed = self._departed, np.zeros_like(
            self._departed)
        if (self.placement == "lexmm"
                and self.mechanism not in ("psdsf-rdm", "psdsf-tdm")):
            return self._solve_lexmm_host()
        with span("churn.solve.upload"):
            up = jax.device_put((self.active, departed,
                                 self.cap_scale.astype(np.float32)))
            count("h2d_bytes", sum(a.nbytes for a in up))
        # the host blocks here rather than in the download below, so the
        # download span times the copy alone
        with span("churn.solve.wait"):
            out = jax.block_until_ready(self._resolve(
                self._demands, self._caps, self._weights, self._elig, *up, x0,
                mechanism=self.mechanism, max_rounds=self.max_rounds,
                tol=self.tol, placement=self.placement, fill=self.fill,
                round=self.round,
                buckets=(None if self._blayout is None
                         else (self._idx_j, self._mask_j)),
                accel=self.accel))
        # the scalars alone come back; the allocation stays on the device
        with span("churn.solve.download"):
            got = jax.device_get(out[1:])
            count("d2h_bytes", sum(a.nbytes for a in out[1:]))
        rounds, resid = int(got[0]), float(got[1])
        hits, rejects = ((int(got[2]), int(got[3]))
                         if self.accel == "anderson" else (0, 0))
        self._resolved = _Resolved(up[0], up[2], out[0], float(got[-1]),
                                   float(got[-2]))
        return _OnDevice(out[0]), rounds, resid, hits, rejects

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
        self._router_stats = self._resolved = None
        with span("churn.solve") as solve_span:
            x, rounds, resid, hits, rejects = self._solve(
                self._x_dev if self.warm_start else None)
        rs = self._router_stats          # lexmm ticks only, else None
        resolved = self._resolved        # jitted resolve ticks, else None
        cold_rounds = -1
        if self.compare_cold and self.warm_start:
            with span("churn.cold"):
                _, cold_rounds, *_ = self._solve(None)
        if isinstance(x, _OnDevice):
            self._x_dev, self._x_host = x.array, None
            total = resolved.total
        else:                            # an answer on the host
            self._x_dev, self._x_host = None, x
            total = float(x.sum())
        mn, arg = (self._min_vds(resolved) if self.telemetry
                   else (np.inf, -1))
        from repro.core.placement import fill_iter_budget

        psdsf = self.mechanism in ("psdsf-rdm", "psdsf-tdm")
        swept = rs is None and (psdsf or self.placement != "headroom")
        budget = (rounds * self.problem.num_servers * fill_iter_budget(
            self.problem.num_resources,
            "tdm" if self.mechanism == "psdsf-tdm" else "rdm", self.fill)
            if swept else 0)
        # tight-tol certification on the active-gamma scale the resolve
        # accepted on (routed/lexmm ticks are one-shot exact); a ``_solve``
        # that bypassed the resolve leaves none, and the scale's floor of 1
        # is the strictest
        if swept:
            with span("churn.certify"):
                scale = 1.0 if resolved is None else resolved.scale
                tight = resid <= self.tol * scale
        else:
            tight = resid == 0.0
        return ChurnRecord(
            time=time_now, n_events=len(events), rounds=rounds,
            cold_rounds=cold_rounds, residual=resid,
            active_users=int(self.active.sum()),
            total_tasks=total, solve_ms=solve_span.ms,
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
    def _min_vds(self, resolved: Optional[_Resolved]) -> tuple[float, int]:
        """Global min normalized VDS (Eq. 16) and the server attaining it.
        The kernel's inputs are built on the device from the state the
        resolve left there (``resolved``; a host-solved tick uploads its
        activity, degrade scales and allocation instead), and only the
        per-server minima come back."""
        import jax.numpy as jnp

        from repro.kernels.psdsf_vds.ops import (_vds_blocks, _vds_interpret,
                                                 vds_argmin)

        with span("churn.telemetry"):
            if resolved is None:
                state = (jnp.asarray(self.active),
                         jnp.asarray(self.cap_scale, jnp.float32),
                         jnp.asarray(self.x, jnp.float32))
                count("h2d_bytes", sum(a.nbytes for a in state))
            else:
                state = resolved[:3]
            inputs = _eq16_inputs_fn()(
                self._demands, self._caps, self._weights, self._elig, *state)
            k = self.problem.num_servers
            block_n, block_k = _vds_blocks(self.problem.num_users, k)
            with span("vds.call"):
                mn, _ = vds_argmin(*inputs, block_n=block_n, block_k=block_k,
                                   interpret=_vds_interpret())
                mn = np.asarray(mn)
                count("d2h_bytes", mn.nbytes)
            i = int(np.argmin(mn[:k]))
            return float(mn[i]), i

    @property
    def x(self) -> np.ndarray:
        """The current allocation, (N, K) float64, as a read-only view: the
        float32 device copy widened, downloaded on the first read after a
        step (under its own ``churn.x.read`` span) and kept until the
        next; rows of users departed since are zero."""
        if self._x_host is None:
            with Tracer("churn.x.read"):
                x = _OnDevice(self._x_dev).copy()
                count("d2h_bytes", self._x_dev.nbytes)
            x[self._departed] = 0.0
            self._x_host = x
            self.x_reads += 1
        view = self._x_host.view()
        view.flags.writeable = False
        return view

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
    mirrors when ``placement="headroom"``). ``x0`` (None: cold) is zeroed
    for users not ``active`` or ``departed`` since it was computed. Returns
    the sweep's ``(x, rounds, residual)``, with ``accel_hits,
    accel_rejects`` under ``accel="anderson"``, then ``x.sum()`` and last
    the certificate's scale. Cached so all
    simulator instances share one jit cache (one compilation per
    (mechanism, placement, shapes))."""
    import functools

    import jax.numpy as jnp
    import jax

    from repro.core.baselines_jax import _routed_fill, level_rate_matrix_jnp
    from repro.core.psdsf_jax import (_check_accel, _full_layout,
                                      _repack_refill_core, _solve_core,
                                      gamma_matrix_jnp)

    @functools.partial(jax.jit, static_argnames=("mechanism", "max_rounds",
                                                 "placement", "fill",
                                                 "round", "accel"))
    def resolve(demands, capacities, weights, eligibility, active, departed,
                cap_scale, x0, *, mechanism, max_rounds, tol,
                placement="level", fill="event", round="gauss",
                buckets=None, accel="none"):
        _check_accel(accel)
        caps_eff = capacities * cap_scale[:, None]
        g = gamma_matrix_jnp(demands, caps_eff, eligibility)
        g = jnp.where(active[:, None], g, 0.0)
        # the certificate's scale, returned last on every path: the ACTIVE
        # users' largest per-server gamma, floored at 1 (the baseline level
        # rates sum gamma over servers — see baselines_jax; and a departed
        # huge-gamma user must not loosen it)
        scale = jnp.maximum(1.0, g.max())
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
            if buckets is not None:
                raise ValueError("routed headroom fill has no bucketed "
                                 "form; guarded in ChurnSimulator.__init__")
            out = _routed_fill(demands, caps_eff, weights, lg, accel)
            return out + (out[0].sum(), scale)
        if x0 is None:
            x0 = jnp.zeros(lg.shape, dtype=demands.dtype)
        x0 = jnp.where((active & ~departed)[:, None], x0, 0.0)
        # departure-only churn masks bucket slots in place: a bucketed
        # layout was built from the active support, so departed users'
        # slots exist and simply go dark under the activity mask
        idx, mask = _full_layout(*lg.shape) if buckets is None else buckets
        out = _solve_core(demands, caps_eff, weights, lg, x0, idx,
                          mask & active[idx], mode, max_rounds, tol,
                          scale=scale, fill=fill, round_mode=round,
                          accel=accel)
        if placement == "headroom":
            fixed = _repack_refill_core(demands, caps_eff, weights, g,
                                        *out[:3], mode, max_rounds, tol,
                                        fill=fill, round_mode=round)
            out = fixed + out[3:]
        return out + (out[0].sum(), scale)

    return resolve


@_functools.lru_cache(maxsize=1)
def _eq16_inputs_fn():
    """Jitted: the Eq. 16 kernel's inputs from device-resident state — the
    effective gamma, zeroed outside ``active & (weights > 0)``, and
    ``x_n / phi_n`` (0 there), both padded to the kernel's blocks. Its own
    program, so the kernel stays one as well (and the gamma read the
    kernel's roofline counts stays the kernel's). The shapes are the dense
    (N, K) ones whatever the layout, so a bucket rebuild recompiles
    nothing here."""
    import jax
    import jax.numpy as jnp

    from repro.core.psdsf_jax import gamma_matrix_jnp
    from repro.kernels.psdsf_vds.ops import _vds_blocks

    @jax.jit
    def eq16_inputs(demands, capacities, weights, eligibility, active,
                    cap_scale, x):
        mask = active & (weights > 0)
        g = gamma_matrix_jnp(demands, capacities * cap_scale[:, None],
                             eligibility)
        g = jnp.where(mask[:, None], g, 0.0)
        x_over_phi = jnp.where(
            mask, x.sum(axis=1) / jnp.where(mask, weights, 1.0), 0.0)
        (n, k), (block_n, block_k) = g.shape, _vds_blocks(*g.shape)
        n_pad, k_pad = -n % block_n, -k % block_k
        return (jnp.pad(x_over_phi, (0, n_pad)),
                jnp.pad(g, ((0, n_pad), (0, k_pad))))

    return eq16_inputs


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
