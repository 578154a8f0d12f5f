"""The ``alibaba2018-churn`` cell: its builder gives the Alibaba 2018
colocated fleet from a seed, with each tenant on a home server and a pool
of the fleet; a tiny drive of the cell on the CPU passes the correctness
check under its limits, the bfloat16 control fails it and the float64
reference reads exact; and the two per-layer metrics that count the
serial class fills read the program in both churn cells, and read nothing
where the program keeps no ``serial_fills`` counter."""
import dataclasses
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from psbench import control, harness, program_spans, reference, registry  # noqa: E402,E501

BENCH = registry.load_benchmark(ROOT)
CELL = "alibaba2018-churn"
CONFIG = "alibaba2018-colo-synth"
GCD = "gcd2011-synth-churn"
TINY = {CELL: dict(servers=64, tenants=256),
        GCD: dict(servers=32, rack_groups=4, tenants=600)}
NEW = ("serial_fills_per_step.churn", "wait_us_per_class_fill.churn")


def _config():
    return registry.config(BENCH, CONFIG)


def _drive(cell, seed):
    """A tiny run of ``cell`` and the ``churn.step`` roots it left. The
    program's ring of roots is shared by every run of the process, so a
    test that reads a run puts its roots back in place (``_ring``)."""
    import jax
    from repro.core import trace

    run = harness.drive(ROOT, cell, seed, 1.5, False,
                        t_start=time.perf_counter(), devices=jax.devices(),
                        log=lambda line: None, config_override=TINY[cell])
    return run, list(trace.recent("churn.step"))


def _ring(drove, monkeypatch):
    from repro.core import trace

    run, roots = drove
    monkeypatch.setattr(trace, "recent", lambda name: list(roots))
    return run


@pytest.fixture(scope="module")
def churn_drove():
    return _drive(CELL, 2**31 + 2018)


@pytest.fixture(scope="module")
def gcd_drove():
    return _drive(GCD, 2**31 + 13)


@pytest.fixture
def churn_run(churn_drove, monkeypatch):
    return _ring(churn_drove, monkeypatch)


def test_the_builder_is_seeded_at_full_size():
    cfg, builder = _config()
    a = builder.build(cfg, np.random.default_rng([0, 3000000019]))
    b = builder.build(cfg, np.random.default_rng([0, 3000000019]))
    c = builder.build(cfg, np.random.default_rng([0, 3000000020]))
    assert a.shape == (2048, 512, 2)
    for field in ("demands", "capacities", "weights", "eligibility"):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field))
    assert not np.array_equal(a.eligibility, c.eligibility)
    assert set(np.unique(a.weights)) <= {1.0, 2.0, 4.0}


def test_every_tenant_has_its_home_server_and_a_pool():
    cfg, builder = _config()
    n, k = cfg["tenants"], cfg["servers"]
    pool = round(cfg["pool_share"] * k)
    assert pool == 102
    home, elig = builder.eligibility(np.random.default_rng(5), n, k, pool)
    assert np.all(np.bincount(home, minlength=k) == n // k)
    assert np.all(elig[np.arange(n), home] == 1.0)
    assert np.all(elig.sum(axis=1) == 1 + pool)
    assert set(np.unique(elig)) == {0.0, 1.0}


def test_capacities_are_the_residual_of_a_normalized_machine():
    cfg, builder = _config()
    d = builder.build(cfg, np.random.default_rng([0, 2**31 + 99]))
    assert d.capacities.shape == (512, 2)
    assert d.capacities.min() >= 0.0 and d.capacities.max() <= 1.0
    taken = builder.online(cfg, np.random.default_rng(1), 512)
    assert taken.min() >= 0.0
    np.testing.assert_array_equal(
        np.clip(1.0 - taken, 0.0, None),
        builder.build(cfg, np.random.default_rng(1)).capacities)


def test_program_answers_pass_the_check(churn_run):
    ok, checks = harness.check(churn_run, registry.limits(CELL))
    assert ok, checks
    assert churn_run.failed == 0 and churn_run.missing == 0
    assert churn_run.window_compiles == 0


def test_control_in_bfloat16_fails_the_check(churn_run):
    tol = _config()[0]["guarantees"]["tol"]
    ctrl = dataclasses.replace(churn_run, samples=[
        control.answer(s, tol) for s in churn_run.samples[:2]])
    ok, checks = harness.check(ctrl, registry.limits(CELL))
    assert not ok, checks


def test_reference_in_float64_passes_the_check(churn_run):
    ref = control.answer(churn_run.samples[0], 1e-9, passes=64,
                         rounding=reference.exact)
    _, checks = harness.check(dataclasses.replace(churn_run, samples=[ref]),
                              registry.limits(CELL))
    for name in ("capacity_excess", "saturation_gap", "vds_rel_err"):
        assert checks[name]["value"] < 1e-9, checks


def test_a_shared_pool_sweeps_one_server_wide(churn_run):
    assert registry.reader("sweep_width.churn").read(churn_run) == 1.0


@pytest.mark.parametrize("cell", [GCD, CELL])
@pytest.mark.parametrize("traced", [False, True])
def test_the_new_metrics_are_reported_in_traced_runs(cell, traced):
    names = {m["name"] for m in registry.metrics(BENCH, cell, traced)}
    assert set(NEW) <= names if traced else not set(NEW) & names


@pytest.fixture(params=["gcd", "alibaba"])
def either_run(request, monkeypatch):
    name = "churn_drove" if request.param == "alibaba" else "gcd_drove"
    return _ring(request.getfixturevalue(name), monkeypatch)


def test_serial_fills_are_the_steps_rounds_times_classes(either_run):
    run = either_run
    found = program_spans.steps(run)
    width = registry.reader("sweep_width.churn").read(run)
    k = run.shape[1]
    want = np.mean([r["rounds"] * k / width for r in run.records])
    got = registry.reader("serial_fills_per_step.churn").read(run)
    assert found and got == pytest.approx(want)
    assert got == np.mean([s.counters["serial_fills"] for s in found])


def test_wait_per_fill_spreads_the_wait_over_the_fills(either_run):
    run = either_run
    found = program_spans.steps(run)
    wait_ms = sum(s.total_ms("churn.solve.wait") for s in found)
    fills = sum(s.counters["serial_fills"] for s in found)
    got = registry.reader("wait_us_per_class_fill.churn").read(run)
    assert got == pytest.approx(1e3 * wait_ms / fills) and got > 0.0
    per_step = registry.reader("solve_wait_ms_per_step.churn").read(run)
    assert got == pytest.approx(1e3 * per_step * len(found) / fills)


@pytest.mark.parametrize("name", NEW)
def test_steps_without_the_counter_read_nothing(churn_run, monkeypatch,
                                                name):
    """A program before the counter (the parent) keeps the step roots but
    no ``serial_fills``: each new metric then reads nothing."""
    bare = [types.SimpleNamespace(counters={"compiles": 0},
                                  total_ms=lambda name: 1.0)
            for _ in program_spans.steps(churn_run)]
    monkeypatch.setattr(program_spans, "steps", lambda run, traced=False:
                        bare)
    assert registry.reader(name).read(churn_run) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_reads_nothing(churn_run, monkeypatch, name):
    from repro.core import trace

    monkeypatch.delattr(trace, "recent")
    assert registry.reader(name).read(churn_run) is None
