"""Open-loop churn: ``ChurnSimulator.step`` driven by seeded events that
fall due on a schedule, whatever the simulator's pace.

Set-up builds the simulator and solves cold to the initial fixed point;
that step runs every program the window runs (a warm step adds none, so
set-up takes none). In the window, each iteration applies every event already due in one
``step()`` and stamps all of them with the time the step returned its
certified quotas; an event's latency counts from its due time, so a slow
step delays the events that fall due while it runs. After the window
closes, the events due in it that are still waiting are applied the same
way. Mix parameters (``traffic/<mix>.json``):

    rate_hz, burst_mean, degrade_share, degrade_scale, restore_after_s
        the event stream (``psbench.events.churn_stream``)
    timeline_seed    fixes when events fall due and how many; the run's
                     seed picks the tenants and servers they hit
    telemetry        the simulator's Eq. 16 telemetry on or off
    trace_seconds    with --trace 1, the last seconds of the window traced
    check_share      share of certified steps kept for the check
    check_steps      at most this many
    drain_limit_s    how long past the close late events are waited for
"""
from __future__ import annotations

import math
import shutil
import time

import numpy as np

from psbench import events, program, tracing
from psbench.harness import Run, Sample


def run(ctx) -> Run:
    mix, d = ctx.mix, ctx.deployment
    max_rounds = ctx.config["guarantees"]["max_rounds"]
    ctx.phase("deployment")
    sim = program.churn_simulator(d, ctx.config["guarantees"],
                                  mix["telemetry"])
    ctx.phase("simulator")
    rec = sim.step([], 0.0)
    ctx.phase("cold_step")
    ctx.log(f"setup cold step rounds={rec.rounds} residual={rec.residual!r} "
            f"certified={rec.rounds_to_tol > 0} solve_ms={rec.solve_ms!r} "
            f"layout={rec.layout} bucket_max={rec.bucket_max} {ctx.clock}")
    x_start = sim.x.copy()
    n, k, _ = d.shape
    stream = events.churn_stream(
        n, k, ctx.seconds, rate_hz=mix["rate_hz"],
        burst_mean=mix["burst_mean"], degrade_share=mix["degrade_share"],
        degrade_scale=tuple(mix["degrade_scale"]),
        restore_after_s=mix["restore_after_s"],
        timeline=np.random.default_rng(mix["timeline_seed"]),
        pick=ctx.rng(1))
    due = np.array([e.due for e in stream])
    todo = [program.churn_event(e) for e in stream]
    pick = ctx.rng(2)
    done_at = np.full(len(stream), np.nan)
    failed = np.zeros(len(stream), dtype=bool)
    records, traced, samples = [], [], []
    capture, summary = None, None
    trace_from = (ctx.seconds - mix["trace_seconds"] if ctx.traced
                  else math.inf)
    wake_late = 0.0
    bad = True
    traces0 = ctx.clock.traces

    def answer(rec) -> Sample:
        """The simulator's current allocation and the state it answers."""
        return Sample(x=sim.x.copy(), demands=d.demands,
                      capacities=d.capacities * sim.cap_scale[:, None],
                      weights=d.weights,
                      eligibility=d.eligibility * sim.active[:, None],
                      min_vds=rec.min_vds if mix["telemetry"] else None)

    ctx.phase("stream")
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"setup phases_s={ctx.phases}")
    t0 = time.perf_counter()
    i = 0
    while i < len(todo):
        now = time.perf_counter() - t0
        if now >= ctx.seconds + mix["drain_limit_s"]:
            break
        if capture is None and now >= trace_from and now < ctx.seconds:
            capture = tracing.Capture(ctx.trace_dir).__enter__()
        if capture is not None and summary is None and now >= ctx.seconds:
            capture.__exit__(None, None, None)
            summary = capture
        if due[i] > now:
            with tracing.span("wait"):
                time.sleep(due[i] - now)
            wake_late = max(wake_late, time.perf_counter() - t0 - due[i])
            continue
        with tracing.span("drain"):
            j = int(np.searchsorted(due, now, side="right"))
            batch = todo[i:j]
        in_trace = capture is not None and summary is None
        t_a = time.perf_counter()
        with tracing.span("step"):
            rec = sim.step(batch, now)
        t_b = time.perf_counter()
        done_at[i:j] = t_b - t0
        bad = rec.rounds >= max_rounds and rec.rounds_to_tol == 0
        failed[i:j] = bad
        (traced if in_trace else records).append(dict(
            start_s=t_a - t0, step_s=t_b - t_a, solve_ms=rec.solve_ms,
            rounds=rec.rounds, certified=not bad, events=j - i,
            rebuilds=rec.layout_rebuilds))
        if (not bad and len(samples) < mix["check_steps"]
                and pick.random() < mix["check_share"]):
            samples.append(answer(rec))
        i = j
    end = time.perf_counter() - t0
    if not samples and not bad:
        # the draw kept nothing: the last step's answer is still the state
        samples.append(answer(rec))
    if capture is not None and summary is None:
        capture.__exit__(None, None, None)
        summary = capture
    window_compiles = ctx.clock.traces - traces0

    trace = None
    if summary is not None:
        trace = summary.summary()
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    answered = ~np.isnan(done_at)
    sizes = [r["events"] for r in records]
    half = len(sizes) // 2
    return Run(
        setup_s=setup_s, window_s=ctx.seconds, attempted=len(stream),
        failed=int(failed.sum()), latencies_s=(done_at - due)[answered],
        completed=int((answered & ~failed).sum()), records=records,
        traced_records=traced, window_compiles=window_compiles, trace=trace,
        samples=samples, missing=int((~answered).sum()), x_start=x_start,
        notes=dict(steps=len(records) + len(traced), end_s=round(end, 3),
                   step_s=[round(r["step_s"], 3) for r in records],
                   rounds=[r["rounds"] for r in records],
                   wake_late_ms=round(float(wake_late) * 1e3, 3),
                   events_per_step_first_half=_mean(sizes[:half]),
                   events_per_step_second_half=_mean(sizes[half:]),
                   max_events_per_step=max(sizes, default=0)))


def _mean(xs) -> float:
    return round(float(np.mean(xs)), 3) if xs else 0.0
