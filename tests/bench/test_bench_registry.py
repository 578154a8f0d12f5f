"""The chip benchmark finds every configuration, traffic mix, metric and
limit by the name BENCHMARK.json gives it, and names what it cannot find."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from psbench import registry  # noqa: E402

BENCH = registry.load_benchmark(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_files(cell):
    entry = registry.cell(BENCH, cell)
    cfg, builder = registry.config(BENCH, entry["config"])
    assert cfg["name"] == entry["config"] and callable(builder.build)
    mix, loop = registry.traffic(entry["traffic"])
    assert callable(loop.run)
    for traced in (False, True):
        for m in registry.metrics(BENCH, cell, traced):
            assert callable(registry.reader(m["name"]).read)
    limits = registry.limits(cell)
    assert limits and all(v > 0 for v in limits.values())


def test_metrics_follow_their_workloads():
    e2e = {m["name"] for m in registry.metrics(BENCH, "gcd2011-synth-churn",
                                               False)}
    assert e2e == {"setup_s", "event_quota_p50_ms", "event_quota_p95_ms"}
    layer = {m["name"] for m in registry.metrics(BENCH, "gcd2011-synth-churn",
                                                 True)}
    assert "host_ms_per_step.churn" in layer


def test_metric_without_workloads_follows_its_end_to_end_metric():
    bench = {"end_to_end": [
        {"name": "a", "workloads": ["c1"]}, {"name": "b"}],
        "per_layer": [{"name": "x", "moves": "a"},
                      {"name": "y", "moves": "b"},
                      {"name": "z", "moves": "a", "workloads": ["c2"]}]}
    assert [m["name"] for m in registry.metrics(bench, "c1", True)] == [
        "x", "y"]
    assert [m["name"] for m in registry.metrics(bench, "c2", True)] == [
        "y", "z"]


@pytest.mark.parametrize("lookup, match", [
    (lambda: registry.cell(BENCH, "no-such-cell"), "unknown workload"),
    (lambda: registry.config(BENCH, "no-such-config"), "unknown config"),
    (lambda: registry.traffic("no-such-mix"), "no file for traffic"),
    (lambda: registry.reader("no_such_metric"), "no file for metric"),
    (lambda: registry.limits("no-such-cell"), "no file for limits"),
])
def test_unknown_names_are_refused_clearly(lookup, match):
    with pytest.raises(registry.UnknownName, match=match):
        lookup()


def test_config_files_state_their_cut_and_guarantees():
    for entry in BENCH["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert set(entry["reduced"]) <= set(cfg["reduced"])
        assert cfg["guarantees"]["precision"] == "float32"
        assert cfg["assumed"]


@pytest.mark.parametrize("name, size", [
    ("gcd2011-synth", dict(servers=32, rack_groups=4, tenants=600)),
    ("gcd2011-synth", dict(servers=64, rack_groups=4, tenants=1024,
                           servers_per_tenant=4)),
])
def test_builders_are_seeded(name, size):
    # every configuration file, whether or not a cell uses it yet
    files = {"configs": [{"name": name,
                          "file": f"benchmarks/chip/configs/{name}.json"}]}
    cfg, builder = registry.config(files, name)
    cfg = {**cfg, **size}
    a = builder.build(cfg, np.random.default_rng([0, 2**31 + 7]))
    b = builder.build(cfg, np.random.default_rng([0, 2**31 + 7]))
    c = builder.build(cfg, np.random.default_rng([0, 2**31 + 8]))
    for f in ("demands", "capacities", "weights", "eligibility"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.demands, c.demands)
    n, k, r = a.shape
    assert (n, k, r) == (size["tenants"], size["servers"], 2)
    assert (a.capacities > 0).all() and (a.demands > 0).all()
    assert (a.eligibility.sum(axis=1) >= 1).all()


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_every_server_holds_the_same_tenant_count(seed):
    """The configuration's balanced placement: one bucket width for every
    seed (so one compiled sweep), each tenant on its stated server count,
    inside its home rack group and the next."""
    cfg, builder = registry.config(BENCH, "gcd2011-synth")
    d = builder.build(cfg, np.random.default_rng([0, seed]))
    e = d.eligibility
    n, k, m = cfg["tenants"], cfg["servers"], cfg["servers_per_tenant"]
    assert (e.sum(axis=0) == m * n // k).all()
    assert (e.sum(axis=1) == m).all()
    groups = cfg["rack_groups"]
    of = np.arange(k) // (k // groups)
    for row in e[:: n // 50]:
        used = np.unique(of[row > 0])
        assert used.size == 1 or (used.size == 2 and used[1] - used[0] in
                                  (1, groups - 1))
