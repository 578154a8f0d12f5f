"""Sparse-eligibility bucket layout + active-set sweep (PR-8 tentpole).

Three layers of guarantees:

* **structure** — ``BucketedLayout`` invariants on degenerate supports
  (empty server buckets, users eligible nowhere, density=1 round-trips to
  dense), the per-row distinct-ids property the collision-free scatters
  rely on, and the CSC ``servers_of`` ripple sets.
* **parity** — dense and bucketed sweeps are the SAME solver: golden
  parity at 1e-9 across mechanisms x fills x backends (numpy, jitted,
  batched, resolve-batched, DistributedPSDSF ticks). Speed is never
  bought with exactness.
* **active-set contract** — on a convergent stream the numpy active-set
  sweep actually skips clean servers AND always finishes with a full
  verification sweep, so its fixed point matches the dense sweep's.
"""
import numpy as np
import pytest

from repro.core import engine
from repro.core.instances import (cell_cluster_instance,
                                  dense_random_instance,
                                  sparse_cell_instance)
from repro.core.layout import (AUTO_DENSITY_MAX, BucketedLayout,
                               resolve_layout)
from repro.core.psdsf import solve_psdsf_rdm, solve_psdsf_tdm
from repro.core.types import AllocationProblem

PARITY_ATOL = 1e-9


@pytest.fixture()
def x64():
    import jax
    with jax.enable_x64(True):
        yield


def _degenerate_problem():
    """Dense random instance with an empty server and an unplaceable user."""
    prob = dense_random_instance(num_users=32, num_servers=8)
    elig = prob.eligibility.copy()
    elig[:, 3] = 0.0               # server 3: nobody eligible
    elig[7, :] = 0.0               # user 7: eligible nowhere
    elig[11, :] = 0.0
    elig[11, 5] = 1.0              # user 11: single-homed
    return AllocationProblem(prob.demands, prob.capacities, prob.weights,
                             elig)


class TestBucketedLayout:
    def test_invariants_on_random_support(self):
        rng = np.random.default_rng(3)
        supp = rng.random((60, 12)) < 0.2
        lay = BucketedLayout.from_support(supp)
        assert lay.nnz == int(supp.sum())
        assert lay.bucket_max == max(int(supp.sum(axis=0).max()), 1)
        for i in range(12):
            np.testing.assert_array_equal(lay.bucket_users(i),
                                          np.nonzero(supp[:, i])[0])
            # padded slots still hold DISTINCT user ids (permutation prefix)
            assert len(set(lay.indices[i].tolist())) == lay.bucket_max
        # CSC side agrees with the CSR side
        for n in range(60):
            np.testing.assert_array_equal(
                np.sort(lay.servers_of(np.array([n]))),
                np.nonzero(supp[n])[0])

    def test_servers_of_ripple_set(self):
        supp = np.zeros((6, 4), dtype=bool)
        supp[0, [0, 2]] = True
        supp[1, [1]] = True
        supp[2, [0, 1, 3]] = True
        lay = BucketedLayout.from_support(supp)
        got = lay.servers_of(np.array([0, 2]))
        assert sorted(got.tolist()) == [0, 0, 1, 2, 3]
        assert lay.servers_of(np.array([3])).size == 0    # eligible nowhere
        assert lay.servers_of(np.array([], dtype=int)).size == 0

    def test_degenerate_supports(self):
        prob = _degenerate_problem()
        lay = BucketedLayout.from_problem(prob)
        assert lay.bucket_users(3).size == 0              # empty server
        assert lay.servers_of(np.array([7])).size == 0    # unplaceable user
        assert (lay.indices[lay.mask] != 7).all()
        # empty support is legal and inert
        empty = BucketedLayout.from_support(np.zeros((4, 3), dtype=bool))
        assert empty.nnz == 0 and empty.density == 0.0
        assert empty.scatter(empty.gather(np.ones((4, 3)))).sum() == 0.0

    def test_density_one_round_trips_to_dense(self):
        rng = np.random.default_rng(0)
        x = rng.uniform(0.0, 5.0, (20, 6))
        lay = BucketedLayout.from_support(np.ones((20, 6), dtype=bool))
        assert lay.density == 1.0 and lay.bucket_max == 20
        np.testing.assert_array_equal(lay.scatter(lay.gather(x)), x)

    def test_gather_scatter_round_trip_on_support(self):
        rng = np.random.default_rng(1)
        supp = rng.random((40, 10)) < 0.3
        lay = BucketedLayout.from_support(supp)
        x = rng.uniform(0.0, 5.0, (40, 10)) * supp
        np.testing.assert_array_equal(lay.scatter(lay.gather(x)), x)

    def test_from_cluster(self):
        from repro.sched import Cluster, TPUPod, TenantJob
        pods = [TPUPod("v5e-a", "v5e", 256, 16, 512, 1600, 100),
                TPUPod("v5p-a", "v5p", 128, 95, 512, 2400, 200)]
        jobs = [TenantJob("a", 1.0, 64, 700, 32, 300, 10),
                TenantJob("b", 1.0, 32, 900, 16, 150, 5,
                          min_hbm_per_chip=90)]       # only fits v5p
        lay = BucketedLayout.from_cluster(Cluster(pods), jobs)
        assert lay.num_servers == 2 and lay.num_users == 2
        assert 1 in lay.servers_of(np.array([1]))
        assert 0 not in lay.servers_of(np.array([1]))

    def test_resolve_layout(self):
        sparse = np.zeros((100, 16), dtype=bool)
        sparse[:, 0] = True
        assert resolve_layout("auto", support=sparse) == "bucketed"
        assert resolve_layout("auto",
                              support=np.ones((100, 16))) == "dense"
        # tiny instances stay dense whatever the density
        assert resolve_layout("auto", support=sparse[:10, :4]) == "dense"
        assert resolve_layout("dense", support=sparse) == "dense"
        assert resolve_layout("bucketed",
                              support=np.ones((4, 2))) == "bucketed"
        with pytest.raises(ValueError):
            resolve_layout("csr", support=sparse)
        assert AUTO_DENSITY_MAX < 1.0


class TestNumpyParity:
    @pytest.mark.parametrize("fill", ["event", "bisect"])
    @pytest.mark.parametrize("solver", [solve_psdsf_rdm, solve_psdsf_tdm])
    def test_dense_vs_bucketed_fixed_point(self, solver, fill):
        prob, _, _ = cell_cluster_instance(num_users=160, num_servers=32,
                                           cells=8, seed=5)
        a_d, i_d = solver(prob, fill=fill, layout="dense")
        a_b, i_b = solver(prob, fill=fill, layout="bucketed")
        assert i_d.layout == "dense" and i_b.layout == "bucketed"
        assert i_b.bucket_max > 0
        np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)
        assert i_b.rounds == i_d.rounds
        assert i_b.residual == pytest.approx(i_d.residual, abs=1e-12)

    def test_degenerate_problem_parity(self):
        prob = _degenerate_problem()
        a_d, _ = solve_psdsf_rdm(prob, layout="dense")
        a_b, i_b = solve_psdsf_rdm(prob, layout="bucketed")
        np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)
        assert a_b.x[7].max() == 0.0 and np.abs(a_b.x[:, 3]).max() == 0.0

    def test_full_density_parity(self):
        prob = dense_random_instance(num_users=40, num_servers=8,
                                     elig_frac=1.0)
        a_d, _ = solve_psdsf_rdm(prob, layout="dense")
        a_b, _ = solve_psdsf_rdm(prob, layout="bucketed")
        np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)

    def test_warm_start_parity(self):
        # fixed round budget + tol=0: both paths run the exact same number
        # of rounds, so the comparison is trajectory-vs-trajectory (ulp
        # noise only) rather than riding the razor-edge acceptance round
        # of the slowly-decaying damped residual
        prob, _, _ = cell_cluster_instance(num_users=128, num_servers=32,
                                           cells=8, seed=2)
        a0, _ = solve_psdsf_rdm(prob, layout="dense")
        caps = prob.capacities.copy()
        caps[3] *= 0.5
        bumped = AllocationProblem(prob.demands, caps, prob.weights,
                                   prob.eligibility)
        a_d, i_d = solve_psdsf_rdm(bumped, x0=a0.x, layout="dense",
                                   tol=0.0, max_rounds=50)
        a_b, i_b = solve_psdsf_rdm(bumped, x0=a0.x, layout="bucketed",
                                   tol=0.0, max_rounds=50)
        np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)
        assert i_b.rounds == i_d.rounds

    def test_bucketed_requires_sweeps(self):
        from repro.core.baselines import solve_tsf
        prob, _, _ = cell_cluster_instance(num_users=64, num_servers=16,
                                           cells=4)
        a_d, _ = solve_tsf(prob, layout="dense")
        a_b, i_b = solve_tsf(prob, layout="bucketed")
        np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)
        assert i_b.layout == "bucketed"
        with pytest.raises(ValueError):
            engine.solve(prob, "drf", layout="bucketed")


class TestActiveSetSweep:
    """The numpy active-set sweep on a CONVERGENT weak-coupling stream:
    servers actually get skipped, the always-run verification sweep keeps
    the certificate a full-sweep one, and at an equal round budget the
    active-set trajectory tracks the dense sweep to ulps.

    Parity runs pin ``tol=0.0`` + a fixed ``max_rounds`` so both layouts
    execute the same rounds: near the acceptance threshold the damped
    residual decays only ~2%/round, so any ulp-level divergence between
    the two (different fill summation groupings) can flip WHICH round
    accepts, moving the reported fixed points apart by ~tol*scale — a
    round-count artifact, not an active-set error. Convergence honesty
    (converged, not approx, with skips) is asserted on a separate
    tolerance-bearing run."""

    def _instance(self):
        # density 0.01875 @ K=64 puts multi-homed users on exactly 2
        # servers (weak coupling): the sweep contracts decisively instead
        # of limit-cycling, which is what lets servers go (and stay) clean
        return sparse_cell_instance(num_users=500, num_servers=64,
                                    density=0.01875, cells=8,
                                    multi_frac=0.2, seed=4)[0]

    def test_skips_happen_and_parity_holds(self):
        prob = self._instance()
        a_d, i_d = solve_psdsf_rdm(prob, layout="dense", tol=0.0,
                                   max_rounds=60)
        a_b, i_b = solve_psdsf_rdm(prob, layout="bucketed", tol=0.0,
                                   max_rounds=60)
        assert i_b.rounds == i_d.rounds == 60
        assert i_b.servers_skipped > 0          # the active set earned keep
        np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)
        assert i_b.residual == pytest.approx(i_d.residual, abs=1e-12)

    def test_self_certified_convergence_with_skips(self):
        # speed is never bought with exactness: the run that skips ~half
        # its server visits still ends converged at full-sweep tolerance
        prob = self._instance()
        _, info = solve_psdsf_rdm(prob, layout="bucketed", tol=1e-6)
        assert info.converged and not info.approx
        assert info.servers_skipped > 0

    def test_churn_stream_parity(self):
        # seeded departure stream: every warm re-solve of the active-set
        # sweep must match the dense full sweep to 1e-9 at equal rounds
        prob = self._instance()
        rng = np.random.default_rng(23)
        a_d0, _ = solve_psdsf_rdm(prob, layout="dense", tol=0.0,
                                  max_rounds=60)
        a_b0, _ = solve_psdsf_rdm(prob, layout="bucketed", tol=0.0,
                                  max_rounds=60)
        x_d, x_b = a_d0.x, a_b0.x
        active = np.ones(prob.num_users, dtype=bool)
        skipped_total = 0
        for step in range(4):
            dep = rng.choice(np.nonzero(active)[0], 12, replace=False)
            active[dep] = False
            x_d[dep] = 0.0
            x_b[dep] = 0.0
            masked = AllocationProblem(
                prob.demands, prob.capacities, prob.weights,
                prob.eligibility * active[:, None])
            a_d, i_d = solve_psdsf_rdm(masked, x0=x_d, layout="dense",
                                       tol=0.0, max_rounds=40)
            a_b, i_b = solve_psdsf_rdm(masked, x0=x_b, layout="bucketed",
                                       tol=0.0, max_rounds=40)
            np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)
            assert i_b.rounds == i_d.rounds
            skipped_total += i_b.servers_skipped
            x_d, x_b = a_d.x, a_b.x
        assert skipped_total > 0

    def test_verification_sweep_is_mandatory(self):
        # the acceptance round must have visited EVERY server: force a
        # tiny max_rounds and check the sweep still reports honestly
        prob = self._instance()
        _, info = solve_psdsf_rdm(prob, layout="bucketed", max_rounds=2)
        # with 2 rounds nothing can be certified unless a full sweep ran;
        # either it converged (visited all) or it reports non-convergence
        assert info.rounds <= 2


class TestJaxParity:
    def test_engine_jax_psdsf_parity(self, x64):
        prob, _ = sparse_cell_instance(num_users=600, num_servers=64,
                                       density=0.05, cells=8, seed=6)
        for mech in ("psdsf-rdm", "psdsf-tdm"):
            a_d, i_d = engine.solve(prob, mech, backend="jax",
                                    layout="dense", fill="bisect",
                                    max_rounds=40)
            a_b, i_b = engine.solve(prob, mech, backend="jax",
                                    layout="bucketed", fill="bisect",
                                    max_rounds=40)
            assert i_b.layout == "bucketed" and i_b.bucket_max > 0
            np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)

    def test_engine_jax_auto_resolves_bucketed(self, x64):
        prob, _ = sparse_cell_instance(num_users=600, num_servers=64,
                                       density=0.05, cells=8, seed=6)
        _, info = engine.solve(prob, "psdsf-rdm", backend="jax",
                               max_rounds=8)
        assert info.layout == "bucketed"

    def test_engine_jax_baseline_parity(self, x64):
        prob, _ = sparse_cell_instance(num_users=400, num_servers=64,
                                       density=0.05, cells=8, seed=8)
        for mech in ("tsf", "cdrfh"):
            a_d, _ = engine.solve(prob, mech, backend="jax",
                                  layout="dense", max_rounds=40)
            a_b, i_b = engine.solve(prob, mech, backend="jax",
                                    layout="bucketed", max_rounds=40)
            assert i_b.layout == "bucketed"
            np.testing.assert_allclose(a_b.x, a_d.x, atol=PARITY_ATOL)

    def test_batched_parity(self, x64):
        import jax.numpy as jnp

        from repro.core.psdsf_jax import batch_problems, psdsf_solve_batched
        probs = [sparse_cell_instance(num_users=200, num_servers=32,
                                      density=0.08, cells=4, seed=s)[0]
                 for s in (0, 1)]
        bat = batch_problems(probs, dtype=np.float64)
        d, c, w, g = (bat["demands"], bat["capacities"], bat["weights"],
                      bat["gamma"])
        lays = [BucketedLayout.from_support(np.asarray(g[j]) > 0)
                for j in range(2)]
        bmax = max(lay.bucket_max for lay in lays)
        idx = np.stack([np.pad(lay.indices,
                               ((0, 0), (0, bmax - lay.bucket_max)))
                        for lay in lays])
        mask = np.stack([np.pad(lay.mask,
                                ((0, 0), (0, bmax - lay.bucket_max)))
                         for lay in lays])
        xb, rb, _ = psdsf_solve_batched(
            d, c, w, g, max_rounds=30, layout="bucketed",
            buckets=(jnp.asarray(idx), jnp.asarray(mask)))
        xd, rd, _ = psdsf_solve_batched(d, c, w, g, max_rounds=30)
        np.testing.assert_allclose(np.asarray(xb), np.asarray(xd),
                                   atol=PARITY_ATOL)
        np.testing.assert_array_equal(np.asarray(rb), np.asarray(rd))

    def test_resolve_batched_parity(self, x64):
        import jax.numpy as jnp

        from repro.core.psdsf_jax import batch_problems, psdsf_resolve_batched
        probs = [sparse_cell_instance(num_users=200, num_servers=32,
                                      density=0.08, cells=4, seed=s)[0]
                 for s in (2, 3)]
        bat = batch_problems(probs, dtype=np.float64)
        d, c, w, g = (bat["demands"], bat["capacities"], bat["weights"],
                      bat["gamma"])
        x0 = jnp.zeros_like(g)
        srv = jnp.asarray(
            np.tile(np.arange(8, dtype=np.int32), (2, 1)))
        lays = [BucketedLayout.from_support(np.asarray(g[j]) > 0)
                for j in range(2)]
        bmax = max(lay.bucket_max for lay in lays)
        idx = np.stack([np.pad(lay.indices,
                               ((0, 0), (0, bmax - lay.bucket_max)))
                        for lay in lays])
        mask = np.stack([np.pad(lay.mask,
                                ((0, 0), (0, bmax - lay.bucket_max)))
                         for lay in lays])
        xb, _, rb, resb = psdsf_resolve_batched(
            d, c, w, g, x0, srv, max_rounds=30, layout="bucketed",
            buckets=(jnp.asarray(idx), jnp.asarray(mask)))
        xd, _, rd, resd = psdsf_resolve_batched(d, c, w, g, x0, srv,
                                                max_rounds=30)
        np.testing.assert_allclose(np.asarray(xb), np.asarray(xd),
                                   atol=PARITY_ATOL)
        np.testing.assert_allclose(np.asarray(resb), np.asarray(resd),
                                   atol=1e-12)


class TestDistributedParity:
    @pytest.mark.parametrize("eng", ["numpy", "jax"])
    def test_tick_parity_with_churn(self, eng):
        prob, _, _ = cell_cluster_instance(num_users=128, num_servers=32,
                                           cells=8, seed=2)
        from repro.core.dynamic import DistributedPSDSF
        d_d = DistributedPSDSF(prob, engine=eng, layout="dense")
        d_b = DistributedPSDSF(prob, engine=eng, layout="bucketed")
        assert d_b.layout == "bucketed" and d_b.bucket_max > 0
        for t in range(5):
            d_d.tick()
            d_b.tick()
            if t == 2:
                d_d.set_active(7, False)
                d_b.set_active(7, False)
        d_d.tick(servers=[1, 5, 9])
        d_b.tick(servers=[1, 5, 9])
        np.testing.assert_allclose(d_b.x, d_d.x, atol=PARITY_ATOL)

    def test_churn_simulator_bucketed_stream(self):
        # f32 jitted sweep: parity at f32 tolerance; the rebuild counter
        # fires exactly when an uncovered user arrives
        from repro.sched.churn import ChurnEvent, ChurnSimulator
        prob, _ = sparse_cell_instance(num_users=300, num_servers=64,
                                       density=0.05, cells=8,
                                       multi_frac=0.2, seed=4)
        act = np.ones(prob.num_users, dtype=bool)
        act[:3] = False
        evs = [ChurnEvent(1.0, "departure", user=10),
               ChurnEvent(2.0, "departure", user=20),
               ChurnEvent(3.0, "arrival", user=1),     # outside the layout
               ChurnEvent(4.0, "degrade", server=2, scale=0.5)]
        sd = ChurnSimulator(prob, initial_active=act.copy(),
                            layout="dense", max_rounds=200)
        sb = ChurnSimulator(prob, initial_active=act.copy(),
                            layout="bucketed", max_rounds=200)
        rd, rb = sd.run(evs), sb.run(evs)
        assert [r.rounds for r in rb] == [r.rounds for r in rd]
        assert rb[0].layout == "bucketed" and rb[0].bucket_max > 0
        assert [r.layout_rebuilds for r in rb] == [0, 0, 1, 0]
        assert sb.layout_rebuilds == 1
        scale = max(float(np.abs(sd.x).max()), 1.0)
        assert float(np.abs(sb.x - sd.x).max()) <= 1e-5 * scale
