"""The chip benchmark's runs, driven here on the CPU at a tiny size with
the chip check skipped: open-loop latency counts from the due time, the
correctness check accepts the program's answers and rejects the bfloat16
control, a perturbed answer, and a run whose timed path is broken."""
import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from psbench import control, harness, program, reference, registry  # noqa: E402,E501

CELL = "gcd2011-synth-churn"
TINY = {CELL: dict(servers=32, rack_groups=4, tenants=600)}


def drive(cell, seed, seconds=1.5):
    import jax

    return harness.drive(ROOT, cell, seed, seconds, False,
                         t_start=time.perf_counter(), devices=jax.devices(),
                         log=lambda line: None, config_override=TINY[cell])


def limits(cell):
    return registry.limits(cell)


@pytest.fixture(scope="module")
def churn_run():
    return drive(CELL, 2**31 + 5)


def test_program_answers_pass_the_check(churn_run):
    ok, checks = harness.check(churn_run, limits(CELL))
    assert ok, checks
    assert churn_run.missing == 0 and churn_run.samples
    assert churn_run.window_compiles == 0


def test_control_in_bfloat16_fails_the_check(churn_run):
    tol = registry.config(registry.load_benchmark(ROOT),
                          "gcd2011-synth")[0]["guarantees"]["tol"]
    ctrl = dataclasses.replace(churn_run, samples=[
        control.answer(s, tol) for s in churn_run.samples[:2]])
    ok, checks = harness.check(ctrl, limits(CELL))
    assert not ok, checks


def test_reference_in_float64_passes_the_check(churn_run):
    """The control's path in bfloat16 fails; the same reference in
    float64 in the program's place reads feasible and saturated."""
    ref = control.answer(churn_run.samples[0], 1e-9, passes=64,
                         rounding=reference.exact)
    _, checks = harness.check(dataclasses.replace(churn_run, samples=[ref]),
                              limits(CELL))
    assert checks["capacity_excess"]["value"] < 1e-9, checks
    assert checks["saturation_gap"]["value"] < 1e-9, checks
    assert checks["vds_rel_err"]["value"] < 1e-9, checks


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_faults_read_at_run_level_fail_the_check(churn_run, fault):
    """The faults ``readings.py`` reads on the chip, here at a tiny size:
    the allocation as the window opened held against a later state, and
    one server's column of an answer scaled."""
    if fault == "unchanged":
        bad = [control.unchanged(s, churn_run.x_start)
               for s in churn_run.samples]
    else:
        bad = [control.altered(s) for s in churn_run.samples]
    ok, checks = harness.check(dataclasses.replace(churn_run, samples=bad),
                               limits(CELL))
    assert not ok, checks


@pytest.mark.parametrize("how", ["scale_up", "scale_down", "drop_user"])
def test_perturbed_answer_fails_the_check(churn_run, how):
    s = churn_run.samples[0]
    x = np.array(s.x, dtype=np.float64)
    if how == "scale_up":
        x[:, 3] *= 1.01
    elif how == "scale_down":
        x[:, 3] *= 0.99
    else:
        x[np.argmax(x[:, 3]), :] = 0.0
    bad = dataclasses.replace(churn_run, samples=[
        dataclasses.replace(s, x=x)])
    ok, checks = harness.check(bad, limits(CELL))
    assert not ok, checks


class _Record:
    def __init__(self, t):
        self.rounds, self.rounds_to_tol, self.residual = 1, 1, 0.0
        self.solve_ms, self.layout, self.bucket_max = 10.0, "bucketed", 1
        self.layout_rebuilds, self.min_vds, self.time = 0, 1.0, t


class _SlowSimulator:
    """Takes 80 ms a step and remembers, for each step, the window time it
    was called at, when it started and ended, and the due times of the
    events it applied."""

    def __init__(self, d):
        n, k, _ = d.shape
        self.x = np.zeros((n, k))
        self.cap_scale = np.ones(k)
        self.active = np.ones(n, bool)
        self.calls = []

    def step(self, batch, now):
        t0 = time.perf_counter()
        time.sleep(0.08)
        self.calls.append((now, t0, time.perf_counter(),
                           [e.time for e in batch]))
        return _Record(now)


def test_open_loop_latency_counts_from_due_time(monkeypatch):
    sims = []

    def fake(d, guarantees, telemetry):
        sims.append(_SlowSimulator(d))
        return sims[-1]

    monkeypatch.setattr(program, "churn_simulator", fake)
    run = drive(CELL, 7, seconds=2.0)
    calls = sims[0].calls[1:]                 # after the set-up step
    want, during = [], 0
    prev_start = None
    for now, start, end, dues in calls:
        window_t0 = start - now               # window clock -> host clock
        want += [end - window_t0 - d for d in dues]
        # events that fell due while the previous step ran
        if prev_start is not None:
            during += sum(d > prev_start - window_t0 for d in dues)
        prev_start = start
    assert len(want) == run.attempted == run.latencies_s.size > 20
    assert during > 0
    np.testing.assert_allclose(np.sort(run.latencies_s), np.sort(want),
                               atol=2e-3)
    assert run.latencies_s.min() >= 0.08 - 1e-3


def _broken_solve(kind):
    def solve(self, x0):
        if kind == "unchanged":
            return self.x.copy(), 1, 0.0, 0, 0
        x, rounds, resid, hits, rejects = solve.real(self, x0)
        x = x.copy()
        x[:, 0] *= 1.05                       # an answer altered
        return x, rounds, resid, hits, rejects
    return solve


@pytest.mark.parametrize("kind", ["unchanged", "altered"])
def test_broken_churn_step_is_not_correct(monkeypatch, kind):
    from repro.sched.churn import ChurnSimulator

    broken = _broken_solve(kind)
    broken.real = ChurnSimulator._solve
    real_step = ChurnSimulator.step
    calls = {"n": 0}

    def step(self, events, time_now):
        calls["n"] += 1
        if calls["n"] > 1:                    # the window's steps only
            self._solve = broken.__get__(self)
        return real_step(self, events, time_now)

    monkeypatch.setattr(ChurnSimulator, "step", step)
    run = drive(CELL, 11)
    ok, checks = harness.check(run, limits(CELL))
    assert not ok, checks

