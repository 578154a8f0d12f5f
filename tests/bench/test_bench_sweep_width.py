"""``sweep_width.churn`` on the tiny CPU drive of the churn cell: the mean
of the ``sweep_width`` counter of the window's steps, K over the number of
colour classes a round fills one after another, and nothing where the
program keeps no such counter."""
import sys
import time
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from psbench import harness, program_spans, registry  # noqa: E402

CELL = "gcd2011-synth-churn"
NAME = "sweep_width.churn"
# 32 servers in 4 rack groups of 8: every tenant of a group shares it, so
# a class holds one server of each of two groups
TINY = dict(servers=32, rack_groups=4, tenants=600)


@pytest.fixture(scope="module")
def churn_run():
    import jax

    return harness.drive(ROOT, CELL, 2**31 + 11, 1.5, False,
                         t_start=time.perf_counter(), devices=jax.devices(),
                         log=lambda line: None, config_override=TINY)


def test_the_metric_is_read_in_traced_runs_of_the_cell():
    bench = registry.load_benchmark(ROOT)
    assert NAME in {m["name"] for m in registry.metrics(bench, CELL, True)}
    assert NAME not in {m["name"]
                        for m in registry.metrics(bench, CELL, False)}


def test_it_reads_the_servers_a_serial_step_fills(churn_run):
    found = program_spans.steps(churn_run, traced=True)
    assert found and all(s.counters["sweep_width"] == 2.0 for s in found)
    assert registry.reader(NAME).read(churn_run) == 2.0


def test_steps_without_the_counter_read_nothing(churn_run, monkeypatch):
    bare = [types.SimpleNamespace(counters={"compiles": 0})
            for _ in program_spans.steps(churn_run, traced=True)]
    monkeypatch.setattr(program_spans, "steps", lambda run, traced: bare)
    assert registry.reader(NAME).read(churn_run) is None


def test_a_program_without_spans_reads_nothing(churn_run, monkeypatch):
    from repro.core import trace

    monkeypatch.delattr(trace, "recent")
    assert registry.reader(NAME).read(churn_run) is None
