"""The per-layer metrics that read the program's own spans, on the tiny
CPU drive of the churn cell: each reads a number, the parts add up to the
step, and where the program keeps too few spans (or none, as a checkout
before them) each reads nothing."""
import dataclasses
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from psbench import harness, program_spans, registry  # noqa: E402

CELL = "gcd2011-synth-churn"
TINY = dict(servers=32, rack_groups=4, tenants=600)
PARTS = ("apply_ms_per_step.churn", "certify_ms_per_step.churn",
         "telemetry_host_ms_per_step.churn",
         "telemetry_call_ms_per_step.churn",
         "solve_transfer_ms_per_step.churn", "solve_wait_ms_per_step.churn",
         "step_uncovered_ms_per_step.churn")
NEW = PARTS + ("transfer_mb_per_step.churn", "step_compiles.churn")


@pytest.fixture(scope="module")
def churn_run():
    import jax

    return harness.drive(ROOT, CELL, 2**31 + 9, 1.5, False,
                         t_start=time.perf_counter(), devices=jax.devices(),
                         log=lambda line: None, config_override=TINY)


def _read(name, run):
    return registry.reader(name).read(run)


def test_the_new_metrics_are_in_the_cell():
    bench = registry.load_benchmark(ROOT)
    names = {m["name"] for m in registry.metrics(bench, CELL, True)}
    assert set(NEW) <= names


@pytest.mark.parametrize("name", NEW)
def test_every_reader_reads_a_number(churn_run, name):
    value = _read(name, churn_run)
    assert value is not None and value >= 0.0
    if name == "transfer_mb_per_step.churn":
        assert value > 0.0


def test_the_parts_add_up_to_the_step(churn_run):
    steps = program_spans.steps(churn_run)
    assert len(steps) == len(churn_run.records) > 0
    step_ms = sum(s.ms for s in steps) / len(steps)
    parts = sum(_read(name, churn_run) for name in PARTS)
    assert parts == pytest.approx(step_ms, rel=0.01)
    outside = (_read("host_ms_per_step.churn", churn_run)
               + _read("solve_ms_per_step.churn", churn_run))
    assert parts == pytest.approx(outside, rel=0.01)


def test_step_compiles_reads_what_window_compiles_reads(churn_run):
    assert _read("step_compiles.churn", churn_run) == (
        churn_run.window_compiles)


def test_steps_pair_with_the_records_in_start_order(churn_run):
    """Each step's span lies inside the harness's clock around the same
    ``step()`` call, and takes nearly all of it."""
    outside = [1e3 * rec["step_s"] - step.ms for step, rec in zip(
        program_spans.steps(churn_run), churn_run.records)]
    assert min(outside) >= -1e-6        # float seconds against integer ns
    assert sorted(outside)[len(outside) // 2] < 0.5


def test_too_few_roots_read_nothing(churn_run):
    from repro.core import trace

    many = [{"start_s": float(i), "step_s": 0.0, "solve_ms": 0.0}
            for i in range(trace.RING_ROOTS + 1)]
    run = dataclasses.replace(churn_run, records=many)
    assert program_spans.steps(run) is None
    for name in NEW:
        assert _read(name, run) is None


def test_a_program_without_spans_reads_nothing(churn_run, monkeypatch):
    from repro.core import trace

    monkeypatch.delattr(trace, "recent")
    for name in NEW:
        assert _read(name, churn_run) is None
