"""The churn step's Eq. 16 telemetry and tight-tol certificate, which read
what the jitted resolve left on the device, against the host reference:
``gamma_matrix`` + ``min_vds_guarded`` on the simulator's allocation, and
``resid <= tol * max(1, largest active gamma)``."""
import sys

import numpy as np
import pytest

from repro.core import gamma as gamma_module
from repro.core.dynamic import min_vds_guarded
from repro.core.instances import sparse_cell_instance
from repro.kernels.psdsf_vds.kernel import BIG
from repro.sched.churn import ChurnEvent, ChurnSimulator

USERS, SERVERS = 200, 32


@pytest.fixture(scope="module")
def prob():
    p, _ = sparse_cell_instance(num_users=USERS, num_servers=SERVERS,
                                density=0.125, cells=8, seed=3)
    return p


#: a cold step, then a degrade, a departure and a restore
STREAM = [
    (0.0, []),
    (1.0, [ChurnEvent(1.0, "degrade", server=3, scale=0.4)]),
    (2.0, [ChurnEvent(2.0, "departure", user=7),
           ChurnEvent(2.0, "departure", user=11)]),
    (3.0, [ChurnEvent(3.0, "restore", server=3)]),
]


def _host_min_vds(sim):
    g = gamma_module.gamma_matrix(sim._effective_problem())
    mn, _ = min_vds_guarded(sim.x, sim.problem.weights, g, sim.active)
    return mn


def _host_scale(sim):
    g = gamma_module.gamma_matrix(sim._effective_problem())
    return float(np.where(sim.active[:, None], g, 0.0).max(initial=1.0))


@pytest.mark.parametrize("kw", [
    dict(mechanism="psdsf-rdm", layout="dense"),
    dict(mechanism="psdsf-rdm", layout="bucketed"),
    dict(mechanism="tsf", layout="dense"),
    dict(mechanism="tsf", layout="bucketed"),
    dict(mechanism="psdsf-rdm", layout="bucketed", accel="anderson"),
    dict(mechanism="psdsf-tdm", layout="dense", placement="headroom"),
    dict(mechanism="tsf", layout="dense", placement="headroom"),
    dict(mechanism="psdsf-rdm", layout="dense", compare_cold=True,
         max_rounds=3),
    dict(mechanism="psdsf-rdm", layout="bucketed", max_rounds=2),
    dict(mechanism="tsf", layout="dense", placement="lexmm"),
], ids=lambda kw: "-".join(f"{v}" for v in kw.values()))
def test_telemetry_and_certificate_match_the_host(prob, kw):
    kw = dict(dict(tol=1e-4, max_rounds=64), **kw)
    sim = ChurnSimulator(prob, **kw)
    for t, events in STREAM:
        rec = sim.step(events, t)
        mn = _host_min_vds(sim)
        assert rec.min_vds == pytest.approx(float(mn.min()), rel=1e-5)
        assert 0 <= rec.bottleneck_server < SERVERS
        assert mn[rec.bottleneck_server] == pytest.approx(
            float(mn.min()), rel=1e-5)
        swept = rec.fill_engine != ""
        tight = (rec.residual <= sim.tol * _host_scale(sim) if swept
                 else rec.residual == 0.0)
        assert rec.rounds_to_tol == (rec.rounds if tight else 0)


def test_the_certificate_sees_both_outcomes(prob):
    """The host-scale comparison above is not vacuous: a converged step
    certifies, and a step cut at its round cap does not."""
    tight = ChurnSimulator(prob, tol=1e-4, max_rounds=64)
    cut = ChurnSimulator(prob, tol=1e-6, max_rounds=1)
    assert tight.step([], 0.0).rounds_to_tol > 0
    rec = cut.step([], 0.0)
    assert rec.rounds == 1 and rec.rounds_to_tol == 0


def test_a_host_solved_tick_uploads_its_state_once(prob):
    """The lexmm router solves on the host, so the telemetry uploads the
    activity, degrade scales and allocation itself, and nothing else."""
    sim = ChurnSimulator(prob, mechanism="tsf", placement="lexmm",
                         tol=1e-4)
    rec = sim.step([], 0.0)
    n, k = USERS, SERVERS
    assert rec.trace.counters["h2d_bytes"] == n + 4 * k + 4 * n * k
    assert rec.trace.counters["d2h_bytes"] == 4 * k


@pytest.mark.parametrize("layout", ["dense", "bucketed"])
def test_a_swept_step_builds_no_host_gamma(prob, monkeypatch, layout):
    sim = ChurnSimulator(prob, layout=layout, tol=1e-4)
    sim.step([], 0.0)
    real = gamma_module.gamma_matrix

    def forbidden(*args, **kwargs):
        raise AssertionError("host gamma_matrix on a swept churn step")

    for name, mod in list(sys.modules.items()):
        if (name.startswith("repro")
                and getattr(mod, "gamma_matrix", None) is real):
            monkeypatch.setattr(mod, "gamma_matrix", forbidden)
    for t, events in STREAM[1:]:
        rec = sim.step(events, t)
        assert rec.fill_engine == "event" and rec.rounds > 0
        assert np.isfinite(rec.min_vds)


@pytest.mark.parametrize("layout", ["dense", "bucketed"])
def test_an_all_departed_fleet_reports_the_sentinel(prob, layout):
    sim = ChurnSimulator(prob, layout=layout, tol=1e-4)
    sim.step([], 0.0)
    rec = sim.step([ChurnEvent(1.0, "departure", user=u)
                    for u in range(USERS)], 1.0)
    assert rec.total_tasks == 0.0 and rec.active_users == 0
    assert rec.min_vds == pytest.approx(BIG)
    assert 0 <= rec.bottleneck_server < SERVERS


def test_without_telemetry_a_step_reports_none(prob):
    sim = ChurnSimulator(prob, telemetry=False, tol=1e-4)
    for t, events in STREAM:
        rec = sim.step(events, t)
        assert rec.min_vds == np.inf and rec.bottleneck_server == -1
        assert rec.rounds_to_tol > 0
    assert "churn.telemetry" not in [s.name for s in rec.trace.walk()]
