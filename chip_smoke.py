"""Smoke run of the churn re-solve path on one TPU chip.

    python chip_smoke.py            # from the repository root, on a TPU host

One process, no subprocesses. It refuses to run without a TPU (nonzero
exit before any solve) and prints one JSON object as its last line only
when every phase passed:

* start: the compile cache (``JAX_COMPILATION_CACHE_DIR`` when set, else
  ``<checkout>/.jax_cache``), the device check, and the device kind and
  count with the jax, jaxlib and libtpu versions;
* phase 1, reference parity on a reduced ``sparse_cell_instance`` (same
  density law, 5,000 users x 128 servers): the jitted engine on the chip
  against the numpy engine, plus the chip's Eq. 16 telemetry against a
  numpy evaluation of Eq. 16 on the same state;
* phase 2, the main path at full size: ``ChurnSimulator`` on the pinned
  20,000 x 256 instance (bucketed layout) through a seeded Poisson stream
  of arrivals, departures and degrades, every step inside the loose
  acceptance band of ``engine.ensure_converged``;
* the compiled ``psdsf_vds`` kernel must lower to ``tpu_custom_call``.

This is a smoke run, not a benchmark: its times are single samples.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.core import engine  # noqa: E402
from repro.core.dynamic import min_vds_guarded  # noqa: E402
from repro.core.gamma import gamma_matrix  # noqa: E402
from repro.core.instances import sparse_cell_instance  # noqa: E402
from repro.core.layout import BucketedLayout  # noqa: E402
from repro.core.properties import check_feasible_rdm  # noqa: E402
from repro.core.psdsf import SolveInfo  # noqa: E402
from repro.core.types import AllocationProblem  # noqa: E402
from repro.sched.churn import ChurnSimulator, poisson_churn_events  # noqa: E402

#: phase 1 size: numpy's bucketed sweep solves it cold in about a minute
PARITY_USERS, PARITY_SERVERS = 5000, 128
#: rounds both engines run undamped in the same server order (the jitted
#: core may start damping after its 4th round, numpy after its 8th), so
#: their iterates are the same sequence up to f32 round-off
TRAJ_ROUNDS = 4
#: f32 bound on per-user totals after TRAJ_ROUNDS rounds, relative to the
#: largest total (f32 eps is 1.2e-7; at 5,000 x 128 the 4 rounds measured
#: 3.1e-7 on a TPU v5e and 1.6e-7 on the CPU backend)
TRAJ_RTOL = 1e-5
#: f32 bound on the Eq. 16 minima (one f32 division against f64)
VDS_RTOL = 1e-5
#: capacity slack admitted for an f32 allocation, relative to capacity
FEAS_TOL = 1e-4
#: Eq. 16 value the kernel reports for a server with no eligible user
VDS_BIG = 3.0e38


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or unaccepted result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _eq16_numpy(x, weights, gamma, active):
    """Eq. 16 in numpy: the (N, K) normalized shares x_n / (phi_n *
    gamma[n, i]) of eligible active users, ``VDS_BIG`` elsewhere; their
    column minima are the per-server minima."""
    live = active[:, None] & (gamma > 0) & (weights[:, None] > 0)
    xphi = x.sum(axis=1) / weights
    return np.where(live, xphi[:, None] / np.where(live, gamma, 1.0),
                    VDS_BIG)


def phase_parity(num_users: int = PARITY_USERS,
                 num_servers: int = PARITY_SERVERS, log=print) -> dict:
    """Phase 1: the jitted engine against the numpy reference.

    (a) ``TRAJ_ROUNDS`` undamped rounds with ``tol=0`` on both engines
    must agree on per-user totals within ``TRAJ_RTOL`` (numpy runs on the
    servers renumbered in the class-major order of the jitted round's
    colouring, so both fill them in one order); (b) cold solves at
    the engine defaults must each pass ``ensure_converged`` (their totals
    gap is printed: on this limit-cycling instance the two damping
    schedules stop at different points of the cycle); (c) ``min_vds`` on
    the jitted solve's state must match numpy's Eq. 16 within
    ``VDS_RTOL``."""
    prob, _ = sparse_cell_instance(num_users=num_users,
                                   num_servers=num_servers)
    g = gamma_matrix(prob)
    scale = max(1.0, float(g.max()))

    colours = BucketedLayout.from_support(g > 0).colours
    order = colours[colours < prob.num_servers]
    in_order = AllocationProblem(prob.demands, prob.capacities[order],
                                 prob.weights, prob.eligibility[:, order])
    ref, _ = engine.solve(in_order, "psdsf-rdm", tol=0.0,
                          max_rounds=TRAJ_ROUNDS)
    got, _ = engine.solve(prob, "psdsf-rdm", backend="jax", tol=0.0,
                          max_rounds=TRAJ_ROUNDS)
    t_ref, t_got = ref.tasks_per_user, got.tasks_per_user
    traj = float(np.abs(t_got - t_ref).max() / max(1.0, t_ref.max()))
    log(f"phase1 size={num_users}x{num_servers}x{prob.num_resources} "
        f"trajectory rounds={TRAJ_ROUNDS} totals_rel_diff={traj!r} "
        f"tol={TRAJ_RTOL!r}")
    _check(np.isfinite(t_got).all(), "phase1: non-finite jitted totals")
    _check(traj <= TRAJ_RTOL,
           f"phase1: trajectory totals differ by {traj!r} > {TRAJ_RTOL!r}")

    t0 = time.perf_counter()
    ref, ref_info = engine.solve(prob, "psdsf-rdm")
    ref_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got, got_info = engine.solve(prob, "psdsf-rdm", backend="jax")
    got_s = time.perf_counter() - t0
    for name, info, secs in (("numpy", ref_info, ref_s),
                             ("jax", got_info, got_s)):
        log(f"phase1 cold {name}: layout={info.layout} "
            f"bucket_max={info.bucket_max} rounds={info.rounds} "
            f"residual={info.residual!r} tight={not info.approx} "
            f"seconds={secs!r}")
        engine.ensure_converged(info, f"phase1 {name} cold solve")
    _check(got_info.layout == "bucketed",
           f"phase1: jitted solve ran layout {got_info.layout!r}")
    ok, msg = check_feasible_rdm(got, tol=FEAS_TOL)
    _check(ok, f"phase1: jitted allocation infeasible: {msg}")
    gap = float(np.abs(got.tasks_per_user - ref.tasks_per_user).max())
    log(f"phase1 cold totals_abs_gap={gap!r} "
        f"max_total={float(ref.tasks_per_user.max())!r} scale={scale!r}")

    active = np.ones(prob.num_users, dtype=bool)
    mn, arg = min_vds_guarded(got.x, prob.weights, g, active)
    shares = _eq16_numpy(got.x, prob.weights, g, active)
    want = shares.min(axis=0)
    at_arg = shares[arg, np.arange(prob.num_servers)]
    vds = float((np.abs(mn - want)
                 / np.maximum(want, np.finfo(np.float32).tiny)).max())
    log(f"phase1 min_vds rel_diff={vds!r} tol={VDS_RTOL!r} "
        f"global_min={float(mn.min())!r}")
    _check(np.allclose(mn, want, rtol=VDS_RTOL, atol=0.0),
           f"phase1: min_vds differs from Eq. 16 by {vds!r}")
    _check(np.allclose(at_arg, want, rtol=VDS_RTOL, atol=0.0),
           "phase1: min_vds argmin does not attain the Eq. 16 minimum")
    return dict(trajectory_rel_diff=traj, vds_rel_diff=vds,
                cold_gap=gap, cold_tight=not got_info.approx)


def _stream(num_users: int, num_servers: int, steps: int, seed: int):
    events = poisson_churn_events(num_users, num_servers, horizon=steps,
                                  arrival_rate=4.0, departure_rate=4.0,
                                  degrade_rate=0.5, seed=seed)
    kinds = {e.kind for e in events}
    _check({"arrival", "departure", "degrade"} <= kinds,
           f"stream seed {seed} lacks an event kind: {sorted(kinds)}")
    return events


def phase_churn(problem=None, steps: int = 10, seed: int = 0,
                log=print) -> dict:
    """Phase 2: ``ChurnSimulator`` at its defaults (``layout="auto"``,
    telemetry on) through a seeded Poisson stream. Step 0 is the cold
    solve to the initial fixed point and carries the compiles; every step
    must sit inside the loose band of ``engine.ensure_converged`` with a
    feasible, finite allocation and a finite ``min_vds``. Each step's
    ``compile_s`` is the ``compile_ms`` counter of its ``churn.step`` span
    (``repro.core.trace``)."""
    prob = sparse_cell_instance()[0] if problem is None else problem
    sim = ChurnSimulator(prob, telemetry=True)
    _check(sim.layout == "bucketed",
           f"phase2: layout='auto' resolved to {sim.layout!r}")
    events = _stream(prob.num_users, prob.num_servers, steps, seed)
    batches: dict = {}
    for ev in events:
        batches.setdefault(ev.time, []).append(ev)
    _check(len(batches) >= steps,
           f"phase2: stream has {len(batches)} steps, want {steps}")

    tight = 0
    records = []
    for t, evs in [(0.0, [])] + sorted(batches.items()):
        rec = sim.step(evs, t)
        compile_s = rec.trace.counters.get("compile_ms", 0.0) / 1e3
        alloc = sim.allocation()
        g_act = np.where(sim.active[:, None],
                         gamma_matrix(alloc.problem), 0.0)
        info = SolveInfo.from_residual(rec.rounds, rec.residual,
                                       float(g_act.max(initial=1.0)),
                                       sim.tol)
        kinds = ",".join(sorted({e.kind for e in evs})) or "none"
        log(f"phase2 step={int(t)} events={rec.n_events} kinds={kinds} "
            f"active={rec.active_users} layout={rec.layout} "
            f"bucket_max={rec.bucket_max} rounds={rec.rounds} "
            f"residual={rec.residual!r} "
            f"tight={rec.rounds_to_tol > 0} "
            f"solve_ms={rec.solve_ms!r} compile_s={compile_s!r} "
            f"min_vds={rec.min_vds!r}")
        engine.ensure_converged(info, f"phase2 step {int(t)}")
        ok, msg = check_feasible_rdm(alloc, tol=FEAS_TOL)
        _check(ok, f"phase2 step {int(t)}: {msg}")
        _check(np.isfinite(alloc.x).all() and np.isfinite(rec.min_vds),
               f"phase2 step {int(t)}: non-finite state or telemetry")
        _check(not alloc.x[~sim.active].any(),
               f"phase2 step {int(t)}: a departed user holds tasks")
        tight += rec.rounds_to_tol > 0
        records.append(dict(rounds=rec.rounds, solve_ms=rec.solve_ms,
                            compile_s=compile_s))
    event_steps = records[1:]
    log(f"phase2 summary steps={len(event_steps)} "
        f"tight_certified={tight}/{len(records)} "
        f"step0_compile_s={records[0]['compile_s']!r} "
        f"step0_solve_ms={records[0]['solve_ms']!r} "
        f"later_compile_s={sum(r['compile_s'] for r in event_steps)!r}")
    return dict(steps=len(event_steps), tight=tight, records=records,
                bucket_max=rec.bucket_max)


def check_vds_compiled(num_users: int, num_servers: int, log=print) -> None:
    """The telemetry kernel at the phase-2 shape must lower to a Mosaic
    ``tpu_custom_call``, i.e. run compiled and not interpreted."""
    from repro.kernels.psdsf_vds.kernel import vds_argmin

    n = num_users + (-num_users % 256)
    k = num_servers + (-num_servers % 128)
    f32 = jax.numpy.float32
    text = vds_argmin.lower(jax.ShapeDtypeStruct((n,), f32),
                            jax.ShapeDtypeStruct((n, k), f32),
                            block_n=256, block_k=128).compile().as_text()
    found = "tpu_custom_call" in text
    log(f"kernel psdsf_vds {n}x{k} tpu_custom_call={found}")
    _check(found, "psdsf_vds did not compile to a tpu_custom_call")


def _versions() -> str:
    from importlib import metadata

    out = []
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out.append(f"{pkg}={metadata.version(pkg)}")
        except metadata.PackageNotFoundError:
            out.append(f"{pkg}=absent")
    return " ".join(out)


def main() -> int:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    print(f"device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} {_versions()} "
          f"cache={jax.config.jax_compilation_cache_dir}", flush=True)

    def log(line):
        print(line, flush=True)

    t0 = time.perf_counter()
    phase_parity(log=log)
    log(f"phase1 ok seconds={time.perf_counter() - t0!r}")
    t0 = time.perf_counter()
    prob = sparse_cell_instance()[0]
    phase_churn(prob, log=log)
    log(f"phase2 ok seconds={time.perf_counter() - t0!r}")
    check_vds_compiled(prob.num_users, prob.num_servers, log=log)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
