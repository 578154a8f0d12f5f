"""The churn step keeps its allocation on the device between steps. Against
an oracle that repeats the host round trip through the same jitted resolve
(a float64 host allocation whose row a departure zeroes, uploaded as the
float32 warm start and downloaded again each step), every step gives the
same allocation, rounds, residual, certificate and Eq. 16 minimum, bit for
bit, and ``total_tasks`` is the host sum to float32 rounding."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.instances import sparse_cell_instance
from repro.core.layout import BucketedLayout
from repro.kernels.psdsf_vds.ops import _vds_blocks, _vds_interpret, vds_argmin
from repro.sched.churn import (ChurnEvent, ChurnSimulator, _eq16_inputs_fn,
                               _resolve_fn)

USERS, SERVERS = 200, 32
MAX_ROUNDS = 64


@pytest.fixture(scope="module")
def prob():
    p, _ = sparse_cell_instance(num_users=USERS, num_servers=SERVERS,
                                density=0.125, cells=8, seed=4)
    return p


def _stream(seed=11):
    """User 0 is absent at the start. A seeded stream with: two
    departures; one user departing and arriving in one batch under a
    degrade; a departed user's return; the arrival of user 0, which the
    bucketed layout never saw (a rebuild); the restore."""
    rng = np.random.default_rng(seed)
    u = [int(v) for v in rng.choice(np.arange(1, USERS), 4, replace=False)]
    s = int(rng.integers(SERVERS))
    return [
        (0.0, []),
        (1.0, [ChurnEvent(1.0, "departure", user=u[0]),
               ChurnEvent(1.0, "departure", user=u[1])]),
        (2.0, [ChurnEvent(2.0, "departure", user=u[2]),
               ChurnEvent(2.0, "arrival", user=u[2]),
               ChurnEvent(2.0, "degrade", server=s, scale=0.5)]),
        (3.0, [ChurnEvent(3.0, "arrival", user=u[0])]),
        (4.0, [ChurnEvent(4.0, "arrival", user=0)]),
        (5.0, [ChurnEvent(5.0, "restore", server=s),
               ChurnEvent(5.0, "departure", user=u[3])]),
    ]


class _HostRoundTrip:
    """The step as it was with the allocation on the host: the same
    resolve and telemetry programs, fed from a float64 host copy."""

    def __init__(self, prob, active, layout, warm_start, compare_cold, kw):
        self.prob, self.active = prob, active.copy()
        self.cap_scale = np.ones(prob.num_servers)
        self.x = np.zeros((prob.num_users, prob.num_servers))
        self.warm_start, self.compare_cold, self.kw = (warm_start,
                                                       compare_cold, kw)
        self.dev = [jnp.asarray(a, jnp.float32) for a in (
            prob.demands, prob.capacities, prob.weights, prob.eligibility)]
        self.layout, self.buckets = layout, None
        if layout == "bucketed":
            self._build()

    def _build(self):
        lay = BucketedLayout.from_support(
            (self.prob.eligibility > 0) & self.active[:, None])
        self.covered = self.active.copy()
        self.buckets = tuple(jnp.asarray(a) for a in (
            lay.indices, lay.mask, lay.colours))

    def _resolve(self, x0):
        return _resolve_fn()(
            *self.dev, jnp.asarray(self.active),
            jnp.zeros(self.prob.num_users, bool),
            jnp.asarray(self.cap_scale, jnp.float32),
            None if x0 is None else jnp.asarray(x0, jnp.float32),
            max_rounds=MAX_ROUNDS, tol=1e-6, buckets=self.buckets,
            **self.kw)

    def step(self, events):
        rebuild = False
        for ev in events:
            if ev.kind == "arrival":
                self.active[ev.user] = True
                rebuild |= self.buckets is not None and not self.covered[
                    ev.user]
            elif ev.kind == "departure":
                self.active[ev.user] = False
                self.x[ev.user] = 0.0
            else:
                self.cap_scale[ev.server] = (ev.scale if ev.kind == "degrade"
                                             else 1.0)
        if rebuild:
            self._build()
        out = self._resolve(self.x if self.warm_start else None)
        cold = (int(self._resolve(None)[1])
                if self.compare_cold and self.warm_start else -1)
        self.x = np.array(out[0], dtype=np.float64)
        rounds, resid, scale = int(out[1]), float(out[2]), float(out[-1])
        inputs = _eq16_inputs_fn()(
            *self.dev, jnp.asarray(self.active),
            jnp.asarray(self.cap_scale, jnp.float32),
            jnp.asarray(self.x, jnp.float32))
        bn, bk = _vds_blocks(self.prob.num_users, self.prob.num_servers)
        mn, _ = vds_argmin(*inputs, block_n=bn, block_k=bk,
                           interpret=_vds_interpret())
        tight = resid <= 1e-6 * scale
        return dict(rounds=rounds, residual=resid, cold_rounds=cold,
                    rounds_to_tol=rounds if tight else 0,
                    min_vds=float(np.asarray(mn)[:self.prob.num_servers]
                                  .min()))


@pytest.mark.parametrize("kw", [
    dict(layout="dense"),
    dict(layout="bucketed"),
    dict(layout="dense", accel="anderson"),
    dict(layout="bucketed", accel="anderson"),
    dict(layout="bucketed", warm_start=False),
    dict(layout="dense", compare_cold=True),
    dict(layout="bucketed", mechanism="tsf"),
    dict(layout="dense", placement="headroom"),
], ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()))
def test_the_device_allocation_matches_the_host_round_trip(prob, kw):
    kw = dict(kw)
    layout = kw.pop("layout")
    warm_start = kw.pop("warm_start", True)
    compare_cold = kw.pop("compare_cold", False)
    kw.setdefault("mechanism", "psdsf-rdm")
    kw.setdefault("placement", "level")
    kw.setdefault("accel", "none")
    active = np.ones(USERS, dtype=bool)
    active[0] = False
    sim = ChurnSimulator(prob, layout=layout, warm_start=warm_start,
                         compare_cold=compare_cold, max_rounds=MAX_ROUNDS,
                         initial_active=active, **kw)
    oracle = _HostRoundTrip(prob, active, sim.layout, warm_start,
                            compare_cold, dict(kw, fill="event",
                                               round="gauss"))
    rebuilds = 0
    for t, events in _stream():
        rec = sim.step(events, t)
        want = oracle.step(events)
        rebuilds += rec.layout_rebuilds
        np.testing.assert_array_equal(sim.active, oracle.active)
        np.testing.assert_array_equal(sim.x, oracle.x)
        for key, value in want.items():
            assert getattr(rec, key) == value, (t, key)
        host_sum = oracle.x.sum()
        assert abs(rec.total_tasks - host_sum) <= 1e-6 * host_sum, t
    assert rebuilds == (layout == "bucketed")
    # a departed user that arrived again in the same batch restarted from
    # zero, as on the host, and every answer went through the device copy
    assert sim.x_reads == len(_stream())
