"""Unified allocator engine: one registry, one convergence contract.

Every mechanism the repo implements — PS-DSF (both feasibility regimes), the
paper's Section II baselines, and the uniform reference point — is exposed
behind one interface::

    alloc, info = get_allocator("tsf")(problem)

An allocator is any callable ``(AllocationProblem, **kw) -> (Allocation,
SolveInfo)``. The ``SolveInfo`` contract is uniform across mechanisms:
``converged`` is True when the residual passed the solver's tight tolerance
OR the loose scheduler tolerance (``approx=True`` in the latter case —
exactly the jax engine's acceptance level); residuals are always reported,
never assumed. ``ensure_converged`` is the shared residual-tolerance check
the scheduling layers use instead of bare asserts.

Registered mechanisms:

  psdsf-rdm   PS-DSF, resource-division multiplexing (the paper's default)
  psdsf-tdm   PS-DSF, time-division multiplexing (Eq. 10 feasibility)
  drf         classic DRF on the pooled cluster — the full-substitutability
              relaxation; the returned Allocation lives on the POOLED
              problem (x shape (N, 1)), see ``baselines.solve_drf_pooled``
  cdrfh       constrained DRFH (exact event-driven level fill)
  tsf         task-share fairness [14] (exact)
  cdrf        constrained DRF [4] (exact)
  uniform     phi-proportional share of every server (closed form)

``solve(problem, mechanism, backend="numpy"|"jax", placement=...)``
additionally routes the sweep-based mechanisms through the jitted engine
(``psdsf_jax`` / ``baselines_jax``) — same fixed points, 10^3-user scales;
closed-form mechanisms (drf, uniform) ignore the backend and accept only
``placement="level"`` (they have no placement freedom). ``placement``
selects the routing strategy from ``core.placement`` (level / headroom /
bestfit / lexmm — the exact lexicographic max-min flow router, which is
mechanism-exact AND packs tightly; its LP certificates always solve
host-side, so ``backend="jax"`` only changes the PS-DSF path, where lexmm
is the identity on the jitted level solve); the returned ``SolveInfo``
records the strategy and the stranded-capacity fraction of the layout.
"""
from __future__ import annotations

from typing import Callable, Dict, Protocol, Tuple

from .baselines import (solve_cdrf, solve_cdrfh, solve_drf_pooled, solve_tsf,
                        uniform_allocation)
from .layout import LAYOUTS
from .placement import ACCEL_ENGINES, get_placement, stranded_fraction
from .psdsf import SolveInfo, solve_psdsf_rdm, solve_psdsf_tdm
from .types import Allocation, AllocationProblem


class ConvergenceError(RuntimeError):
    """A solve ended outside even the loose acceptance tolerance."""


class Allocator(Protocol):
    """Callable signature every registered mechanism implements:
    ``(problem, **kw) -> (Allocation, SolveInfo)``."""

    def __call__(self, problem: AllocationProblem, **kw
                 ) -> Tuple[Allocation, SolveInfo]: ...


_REGISTRY: Dict[str, Allocator] = {}

#: mechanisms realized as Gauss-Seidel sweeps of per-server fills — these
#: run on the jitted jax backend and can tick through the churn simulator
#: (drf/uniform are closed-form: nothing to sweep or warm-start)
SWEEP_MECHANISMS = ("psdsf-rdm", "psdsf-tdm", "cdrfh", "tsf", "cdrf")


def register_allocator(name: str) -> Callable[[Allocator], Allocator]:
    """Decorator registering an :class:`Allocator` under ``name``
    (duplicate names raise so a typo can't shadow a mechanism)."""
    def deco(fn: Allocator) -> Allocator:
        if name in _REGISTRY:
            raise ValueError(f"allocator {name!r} already registered")
        _REGISTRY[name] = fn
        return fn
    return deco


def get_allocator(name: str) -> Allocator:
    """Look up a registered mechanism; unknown names raise with the
    registered list in the message."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown allocator {name!r}; registered: "
                       f"{', '.join(sorted(_REGISTRY))}") from None


def list_allocators() -> Tuple[str, ...]:
    """Sorted names of every registered mechanism."""
    return tuple(sorted(_REGISTRY))


def ensure_converged(info: SolveInfo, what: str = "allocator") -> SolveInfo:
    """Shared acceptance check for scheduling layers.

    Accepts tight or loose (``approx``) convergence — the same level the jax
    engine certifies at — and raises ``ConvergenceError`` (never a stripped
    ``assert``) otherwise, with the residual in the message.
    """
    if not info.converged:
        raise ConvergenceError(
            f"{what}: residual {info.residual:.3e} after {info.rounds} "
            f"rounds exceeds the loose acceptance tolerance")
    return info


register_allocator("psdsf-rdm")(solve_psdsf_rdm)
register_allocator("psdsf-tdm")(solve_psdsf_tdm)
register_allocator("cdrfh")(solve_cdrfh)
register_allocator("tsf")(solve_tsf)
register_allocator("cdrf")(solve_cdrf)


@register_allocator("drf")
def _drf(problem: AllocationProblem, **kw) -> Tuple[Allocation, SolveInfo]:
    # closed form: sweep kwargs (tol, max_rounds, ...) have nothing to
    # control, but the Allocator contract accepts them so callers can sweep
    # mechanisms with shared solver options
    _reject_placement(kw, "drf")
    alloc, info = solve_drf_pooled(problem)
    info.stranded_frac = stranded_fraction(alloc.problem, alloc.x)
    return alloc, info


@register_allocator("uniform")
def _uniform(problem: AllocationProblem, **kw
             ) -> Tuple[Allocation, SolveInfo]:
    _reject_placement(kw, "uniform")
    alloc = uniform_allocation(problem)
    return alloc, SolveInfo(1, True, 0.0,
                            stranded_frac=stranded_fraction(problem, alloc.x))


def _reject_placement(kw: dict, mechanism: str) -> None:
    """Closed-form mechanisms have no placement freedom: drf solves a
    pooled relaxation, uniform IS a fixed placement. Accept only the
    default strategy so a routing request cannot be silently ignored.
    The same applies to the sweep-only ``fill``/``round`` axes — there is
    no per-server fill to run, so only the defaults are accepted."""
    placement = kw.pop("placement", "level")
    get_placement(placement)
    if placement != "level":
        raise ValueError(
            f"mechanism {mechanism!r} is closed-form and has no placement "
            f"freedom; only placement='level' is accepted, got {placement!r}")
    fill = kw.pop("fill", "event")
    rnd = kw.pop("round", "gauss")
    if fill != "event" or rnd != "gauss":
        raise ValueError(
            f"mechanism {mechanism!r} is closed-form and runs no per-server "
            f"fill; only fill='event', round='gauss' are accepted, got "
            f"fill={fill!r}, round={rnd!r}")
    layout = kw.pop("layout", "auto")
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}: {layout!r}")
    if layout == "bucketed":
        raise ValueError(
            f"mechanism {mechanism!r} is closed-form and runs no sweep to "
            f"bucket; only layout='dense'/'auto' are accepted")
    accel = kw.pop("accel", "none")
    if accel not in ACCEL_ENGINES:
        raise ValueError(f"accel must be one of {ACCEL_ENGINES}: {accel!r}")
    if accel != "none":
        raise ValueError(
            f"mechanism {mechanism!r} is closed-form and runs no outer "
            f"iteration to accelerate; only accel='none' is accepted, got "
            f"{accel!r}")


def solve(problem: AllocationProblem, mechanism: str = "psdsf-rdm",
          backend: str = "numpy", placement: str = "level",
          **kw) -> Tuple[Allocation, SolveInfo]:
    """One-call entry point: registry lookup + optional jitted backend.

    Sweep mechanisms additionally accept ``fill="event"|"bisect"`` (the
    per-server fill engine — same fixed point, see
    ``placement.server_fill_rdm_bisect``), ``accel="none"|"anderson"``
    (the safeguarded outer-iteration accelerator, see
    ``placement._anderson_fixed_point`` / ``psdsf_jax._anderson_rounds`` —
    same fixed point, fewer sweeps) and, on the jax backend,
    ``round="gauss"|"jacobi"`` (the outer iteration, see
    ``psdsf_jax._solve_core``); closed-form mechanisms reject all three.

    ``placement`` selects the routing strategy for sweep mechanisms (see
    ``core.placement``); the jax backend accepts the strategies flagged
    ``jax_backend`` in the registry (level, headroom, lexmm — bestfit is
    numpy-only). lexmm under ``backend="jax"`` is the identity on the
    jitted level solve for PS-DSF and runs its LP certificates host-side
    for the global-share mechanisms (``solve_baseline_jax`` routes it).

    lexmm solves go through the warm ``flowrouter.RouterState`` (cached
    certificate matrices + dual-seeded freeze candidates) and surface the
    router's observability on the returned ``SolveInfo`` (``lp_calls``,
    ``lp_iters``, ``stage_ms``, warm-reuse counters); callers that
    re-solve under churn should hold a ``RouterState`` (or use
    ``sched.churn.ChurnSimulator``) to also reuse the solved stage trace
    across ticks.
    """
    if backend not in ("numpy", "jax"):
        raise ValueError(f"backend must be 'numpy' or 'jax': {backend!r}")
    strategy = get_placement(placement)
    if backend == "jax" and mechanism in SWEEP_MECHANISMS:
        if not strategy.jax_backend:
            raise ValueError(
                f"placement {placement!r} has no jitted mirror; use "
                f"backend='numpy' or a jax_backend strategy")
        if mechanism in ("psdsf-rdm", "psdsf-tdm"):
            return _solve_psdsf_via_jax(problem, mechanism,
                                        placement=placement, **kw)
        from .baselines_jax import solve_baseline_jax
        return solve_baseline_jax(problem, mechanism, placement=placement,
                                  **kw)
    if mechanism in SWEEP_MECHANISMS:
        rnd = kw.pop("round", "gauss")
        if rnd != "gauss":
            raise ValueError(
                f"round={rnd!r} needs the vmapped sweep: use backend='jax' "
                f"(the numpy sweep is Gauss-Seidel by construction)")
    return get_allocator(mechanism)(problem, placement=placement, **kw)


def _solve_psdsf_via_jax(problem: AllocationProblem, mechanism: str, x0=None,
                         max_rounds: int = 256, tol: float = 1e-6,
                         loose_tol: float = 5e-3, placement: str = "level",
                         fill: str = "event", round: str = "gauss",
                         layout: str = "auto", accel: str = "none"
                         ) -> Tuple[Allocation, SolveInfo]:
    import jax.numpy as jnp
    import numpy as np

    from .gamma import gamma_matrix
    from .layout import BucketedLayout, resolve_layout
    from .placement import fill_iter_budget
    from .psdsf_jax import psdsf_solve_jax

    g = gamma_matrix(problem)
    mode = "rdm" if mechanism == "psdsf-rdm" else "tdm"
    # "auto" resolves host-side (the jitted entries take pre-built
    # buckets, None for dense; density inspection can't trace)
    resolved = resolve_layout(layout, support=g)
    buckets = None
    bucket_max = 0
    if resolved == "bucketed":
        blayout = BucketedLayout.from_support(g > 0)
        buckets = (jnp.asarray(blayout.indices), jnp.asarray(blayout.mask))
        bucket_max = blayout.bucket_max
    out = psdsf_solve_jax(
        jnp.asarray(problem.demands), jnp.asarray(problem.capacities),
        jnp.asarray(problem.weights), jnp.asarray(g),
        x0=None if x0 is None else jnp.asarray(x0),
        mode=mode, max_rounds=max_rounds, tol=tol, placement=placement,
        fill=fill, round=round, buckets=buckets, accel=accel)
    x, rounds, resid = out[0], out[1], out[2]
    hits, rejects = (int(out[3]), int(out[4])) if accel == "anderson" \
        else (0, 0)
    x = np.asarray(x, dtype=np.float64)
    return (Allocation(problem, x),
            SolveInfo.from_residual(int(rounds), float(resid),
                                    float(g.max(initial=1.0)), tol,
                                    loose_tol, placement=placement,
                                    stranded_frac=stranded_fraction(
                                        problem, x, gamma=g),
                                    fill_engine=fill,
                                    fill_iters=int(rounds) *
                                    problem.num_servers *
                                    fill_iter_budget(problem.num_resources,
                                                     mode, fill),
                                    layout=resolved, bucket_max=bucket_max,
                                    accel=accel, accel_hits=hits,
                                    accel_rejects=rejects))
