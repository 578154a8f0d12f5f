"""The ``serial_fills`` counter of a churn step: the step's sweep rounds
times the server classes a round fills one after another (the layout's
colour classes, K on the dense layout, 1 under a Jacobi round), on every
``churn.step`` root, beside ``sweep_width`` (K over those classes)."""
import numpy as np
import pytest

from repro.core.instances import sparse_cell_instance
from repro.sched.churn import ChurnEvent, ChurnSimulator

USERS, SERVERS = 240, 32
BATCHES = [[ChurnEvent(1.0, "departure", user=3),
            ChurnEvent(1.0, "departure", user=40)],
           [ChurnEvent(2.0, "degrade", server=5, scale=0.5)],
           [ChurnEvent(3.0, "arrival", user=3),
            ChurnEvent(3.0, "restore", server=5)]]


def _classes(sim):
    if sim.round == "jacobi":
        return 1
    if sim.layout == "dense":
        return SERVERS
    return sim._blayout.colours.shape[0]


@pytest.mark.parametrize("layout, round_mode", [
    ("bucketed", "gauss"), ("dense", "gauss"), ("bucketed", "jacobi")])
def test_serial_fills_are_rounds_times_classes(layout, round_mode):
    prob, _ = sparse_cell_instance(num_users=USERS, num_servers=SERVERS,
                                   density=0.1875, cells=8)
    sim = ChurnSimulator(prob, layout=layout, round=round_mode, tol=1e-4)
    recs = [sim.step([], 0.0)] + [sim.step(b, b[0].time) for b in BATCHES]
    classes = _classes(sim)
    if layout == "bucketed" and round_mode == "gauss":
        assert 1 < classes < SERVERS       # the colouring is in play
    for rec in recs:
        counters = rec.trace.counters
        assert rec.rounds > 0
        assert counters["serial_fills"] == rec.rounds * classes
        assert counters["sweep_width"] == SERVERS / classes


def test_a_shared_pool_fills_one_server_a_step():
    """Where every pair of servers shares a user the colouring gives K
    classes of one, and a step's serial fills are its rounds times K."""
    rng = np.random.default_rng(7)
    prob, _ = sparse_cell_instance(num_users=USERS, num_servers=SERVERS,
                                   density=0.1875, cells=8)
    elig = np.ones((USERS, SERVERS))
    elig[rng.random((USERS, SERVERS)) < 0.3] = 0.0
    prob = type(prob)(prob.demands, prob.capacities, prob.weights, elig)
    sim = ChurnSimulator(prob, layout="bucketed", tol=1e-4)
    assert sim._blayout.colours.shape == (SERVERS, 1)
    rec = sim.step([], 0.0)
    assert rec.trace.counters["serial_fills"] == rec.rounds * SERVERS
    assert rec.trace.counters["sweep_width"] == 1.0
