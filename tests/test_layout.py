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
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import engine
from repro.core.instances import (cell_cluster_instance,
                                  dense_random_instance,
                                  sparse_cell_instance)
from repro.core.layout import (AUTO_DENSITY_MAX, BucketedLayout,
                               resolve_layout)
from repro.core.psdsf import solve_psdsf_rdm, solve_psdsf_tdm
from repro.core.properties import check_feasible_rdm
from repro.core.types import Allocation, AllocationProblem

PARITY_ATOL = 1e-9
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture()
def x64():
    import jax
    with jax.enable_x64(True):
        yield


def _host_full_layout(n, k):
    """The full layout built on the host, as a sparse one is: the arrays
    ``buckets=None`` must match bit for bit."""
    import jax.numpy as jnp
    lay = BucketedLayout.from_support(np.ones((n, k)))
    return jnp.asarray(lay.indices), jnp.asarray(lay.mask)


def _rack_group_problem(seed, servers, tenants, rack_groups):
    """The churn cell's deployment (its own build, rack groups and all) cut
    to ``servers`` x ``tenants``."""
    sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
    from psbench import registry
    cfg, deployment = registry.config(registry.load_benchmark(ROOT),
                                   "gcd2011-synth")
    d = deployment.build(dict(cfg, servers=servers, tenants=tenants,
                           rack_groups=rack_groups),
                      np.random.default_rng(seed))
    return AllocationProblem(d.demands, d.capacities, d.weights,
                             d.eligibility)


def _class_major(colours, k):
    """The server order a coloured round fills in: class by class,
    sentinels dropped."""
    return colours[colours < k]


def _in_class_major_order(prob, support):
    """``prob`` with its servers renumbered in the class-major order of the
    bucketed layout of ``support``, and that order: a dense sweep of it
    (one server a step, in id order) visits the servers as a coloured
    round of ``prob`` does."""
    order = _class_major(BucketedLayout.from_support(support).colours,
                         prob.num_servers)
    return AllocationProblem(prob.demands, prob.capacities[order],
                             prob.weights, prob.eligibility[:, order]), order


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


def _colouring_support(support):
    """(support, expected colours shape or None) of a named case."""
    if support.startswith("random-"):
        seed, frac = support.split("-")[1:]
        rng = np.random.default_rng(int(seed))
        return rng.random((80, 24)) < float(frac), None
    if support == "degenerate":
        return _degenerate_problem().eligibility > 0, None
    if support == "dense":
        return np.ones((40, 12), dtype=bool), (12, 1)
    seed = int(support.removeprefix("rack-groups-"))
    prob = _rack_group_problem(seed, 256, 5120, 16)
    return prob.eligibility > 0, (16, 16)


class TestColouring:
    """``BucketedLayout.colours`` colours the servers' conflict graph (two
    servers conflict when they share a user), and a round that fills each
    class at once is the one-server-a-step round in class-major order."""

    @pytest.mark.parametrize("support", [
        "random-0-0.05", "random-1-0.2", "random-2-0.5", "degenerate",
        "rack-groups-4100000023", "rack-groups-2900000057",
        "rack-groups-1", "dense"])
    def test_colouring_is_valid(self, support):
        supp, shape = _colouring_support(support)
        k = supp.shape[1]
        col = BucketedLayout.from_support(supp).colours
        assert col.dtype == np.int32 and col.ndim == 2
        if shape is not None:
            assert col.shape == shape
        # every server exactly once; the rest of the array is the sentinel
        np.testing.assert_array_equal(np.sort(col[col < k]), np.arange(k))
        assert (col[col >= k] == k).all()
        for cls in col:
            srv = cls[cls < k]
            assert (np.diff(srv) > 0).all()            # ascending
            assert (cls[srv.size:] == k).all()         # padded at the end
            # no user on two servers of one class
            assert supp[:, srv].sum(axis=1).max(initial=0) <= 1

    @pytest.mark.parametrize("accel", ["none", "anderson"])
    @pytest.mark.parametrize("dtype", ["float32", "float64"])
    @pytest.mark.parametrize("support", ["rack-groups", "padded"])
    def test_coloured_round_is_the_class_major_sweep(self, support, dtype,
                                                     accel):
        import jax
        import jax.numpy as jnp

        from repro.core.gamma import gamma_matrix
        from repro.core.psdsf_jax import _solve_core
        if support == "padded":
            prob, _ = sparse_cell_instance(num_users=600, num_servers=64,
                                           density=0.05, cells=8, seed=6)
        else:
            prob = _rack_group_problem(7, 64, 1280, 4)
        k = prob.num_servers
        g = gamma_matrix(prob)
        lay = BucketedLayout.from_support(g > 0)
        assert (lay.colours == k).any() == (support == "padded")
        assert lay.colours.shape[0] < k
        with jax.enable_x64(dtype == "float64"):
            args = [jnp.asarray(a, dtype) for a in (
                prob.demands, prob.capacities, prob.weights, g,
                np.zeros(g.shape))]
            buckets = jnp.asarray(lay.indices), jnp.asarray(lay.mask)

            def solve(**sweep):
                return jax.jit(lambda d, c, w, gg, x0: _solve_core(
                    d, c, w, gg, x0, *buckets, "rdm", 80, 1e-5,
                    accel=accel, **sweep))(*args)

            got = solve(colours=jnp.asarray(lay.colours))
            want = solve(servers=jnp.asarray(_class_major(lay.colours, k)))
        scale = max(1.0, float(g.max()))
        assert int(got[1]) == int(want[1])
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=0, atol=1e-6 * scale)
        assert float(got[2]) == pytest.approx(float(want[2]),
                                              abs=1e-6 * scale)

    @pytest.mark.parametrize("path", ["dense", "tick", "batched"])
    def test_sequential_sweeps_keep_their_order(self, x64, path):
        import jax
        import jax.numpy as jnp

        from repro.core.gamma import gamma_matrix
        from repro.core.psdsf_jax import (_solve_core, psdsf_solve_batched,
                                          psdsf_solve_jax)
        prob, _ = sparse_cell_instance(num_users=200, num_servers=32,
                                       density=0.08, cells=4, seed=0)
        n, k = prob.num_users, prob.num_servers
        g = gamma_matrix(prob)
        args = [jnp.asarray(a) for a in (prob.demands, prob.capacities,
                                         prob.weights, g)]
        one_a_step = jnp.arange(k, dtype=jnp.int32)[:, None]
        if path == "dense":
            # buckets=None: the full layout, one server a step
            x, rounds, _ = psdsf_solve_jax(*args, max_rounds=30)
            idx, mask = _host_full_layout(n, k)
            x1, rounds1, _ = psdsf_solve_jax(
                *args, max_rounds=30, buckets=(idx, mask, one_a_step))
        elif path == "tick":
            # the servers= tick: that list, in its order, one a step
            from repro.core.dynamic import DistributedPSDSF
            dist = DistributedPSDSF(prob, engine="jax", layout="bucketed")
            dist.tick()
            x0 = jnp.asarray(dist.x)
            order = np.array([9, 1, 30, 5, 17, 2], dtype=np.int32)
            dist.tick(servers=order)
            x, rounds = dist.x, 1
            lay = BucketedLayout.from_support(g > 0)
            x1, rounds1, _ = jax.jit(lambda *a: _solve_core(
                *a, x0, jnp.asarray(lay.indices), jnp.asarray(lay.mask),
                "rdm", 1, 0.0,
                colours=jnp.asarray(order)[:, None]))(*args)
        else:
            # the batched entries take (idx, mask) stacks: one a step
            lay = BucketedLayout.from_support(g > 0)
            idx, mask = jnp.asarray(lay.indices), jnp.asarray(lay.mask)
            x, rounds, _ = psdsf_solve_batched(
                *(a[None] for a in args), max_rounds=30,
                buckets=(idx[None], mask[None]))
            x, rounds = x[0], rounds[0]
            x1, rounds1, _ = psdsf_solve_jax(
                *args, max_rounds=30, buckets=(idx, mask, one_a_step))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(x1))
        assert int(rounds) == int(rounds1)

    def test_churn_coloured_and_sequential_certify_alike(self, monkeypatch):
        from repro.core import layout as layout_mod
        from repro.sched.churn import ChurnEvent, ChurnSimulator
        prob = _rack_group_problem(11, 64, 1280, 4)
        act = np.ones(prob.num_users, dtype=bool)
        act[:3] = False
        batches = [[ChurnEvent(1.0, "departure", user=10),
                    ChurnEvent(1.0, "departure", user=20)],
                   [ChurnEvent(2.0, "arrival", user=1)],   # a rebuild
                   [ChurnEvent(3.0, "degrade", server=2, scale=0.5)],
                   [ChurnEvent(4.0, "departure", user=500),
                    ChurnEvent(4.0, "arrival", user=10)],  # rebuilds
                   [ChurnEvent(5.0, "restore", server=2)]]
        tol = 1e-4

        def sim():
            return ChurnSimulator(prob, initial_active=act.copy(),
                                  layout="bucketed", tol=tol)

        def one_server_a_step(m):
            m.setattr(layout_mod, "_greedy_colours",
                      lambda indices, *_: np.arange(
                          indices.shape[0], dtype=np.int32)[:, None])

        coloured = sim()
        with monkeypatch.context() as m:
            one_server_a_step(m)
            sequential = sim()
        classes = coloured._blayout.colours.shape[0]
        assert classes < prob.num_servers
        rebuilds = []
        for batch in batches:
            with monkeypatch.context() as m:     # rebuilds colour again
                one_server_a_step(m)
                s = sequential.step(batch, batch[0].time)
            c = coloured.step(batch, batch[0].time)
            rebuilds.append(c.layout_rebuilds)
            assert c.rounds_to_tol > 0 and s.rounds_to_tol > 0
            assert c.trace.counters["sweep_width"] == 64 / classes
            assert s.trace.counters["sweep_width"] == 1.0
            scale = coloured._resolved.scale
            assert scale == sequential._resolved.scale
            # the split of a user's tasks over its servers is not unique
            # (the two orders end ~1% of scale apart on single entries),
            # so the answers are compared on per-user totals: each sums
            # the user's 8 server shares, each certified to tol x scale
            gap = np.abs(coloured.x.sum(1) - sequential.x.sum(1)).max()
            assert gap <= 8 * tol * scale
            for done in (coloured, sequential):
                ok, msg = check_feasible_rdm(done.allocation(), tol=1e-4)
                assert ok, msg
        assert rebuilds == [0, 1, 0, 1, 0]


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
    @pytest.mark.parametrize("support", ["eligible", "full"])
    def test_engine_jax_psdsf_parity(self, x64, support):
        import jax.numpy as jnp

        from repro.core.gamma import gamma_matrix
        from repro.core.psdsf_jax import psdsf_solve_jax
        prob, _ = sparse_cell_instance(num_users=600, num_servers=64,
                                       density=0.05, cells=8, seed=6)
        perm, order = _in_class_major_order(prob, gamma_matrix(prob) > 0)
        for mech in ("psdsf-rdm", "psdsf-tdm"):
            if support == "full":
                a_d, i_d = engine.solve(prob, mech, backend="jax",
                                        layout="dense", fill="bisect",
                                        max_rounds=40)
                # the dense solve passed buckets=None: the full layout
                x, rounds, _ = psdsf_solve_jax(
                    *(jnp.asarray(a) for a in (
                        prob.demands, prob.capacities, prob.weights,
                        gamma_matrix(prob))),
                    mode=mech.removeprefix("psdsf-"), fill="bisect",
                    max_rounds=40, buckets=_host_full_layout(
                        prob.num_users, prob.num_servers))
                np.testing.assert_array_equal(np.asarray(x), a_d.x)
                assert int(rounds) == i_d.rounds
                continue
            # the bucketed round fills colour classes: the same sweep as
            # the dense one over the servers in class-major order
            a_d, i_d = engine.solve(perm, mech, backend="jax",
                                    layout="dense", fill="bisect",
                                    max_rounds=40)
            a_b, i_b = engine.solve(prob, mech, backend="jax",
                                    layout="bucketed", fill="bisect",
                                    max_rounds=40)
            assert i_b.layout == "bucketed" and i_b.bucket_max > 0
            np.testing.assert_allclose(a_b.x[:, order], a_d.x,
                                       atol=PARITY_ATOL)
            assert i_b.rounds == i_d.rounds

    def test_engine_jax_auto_resolves_bucketed(self, x64):
        prob, _ = sparse_cell_instance(num_users=600, num_servers=64,
                                       density=0.05, cells=8, seed=6)
        _, info = engine.solve(prob, "psdsf-rdm", backend="jax",
                               max_rounds=8)
        assert info.layout == "bucketed"

    def test_engine_jax_baseline_parity(self, x64):
        from repro.core.baselines import level_rate_matrix
        prob, _ = sparse_cell_instance(num_users=400, num_servers=64,
                                       density=0.05, cells=8, seed=8)
        for mech in ("tsf", "cdrfh"):
            # the dense sweep over the bucketed round's class-major order
            perm, order = _in_class_major_order(
                prob, level_rate_matrix(prob, mech) > 0)
            a_d, i_d = engine.solve(perm, mech, backend="jax",
                                    layout="dense", max_rounds=40)
            a_b, i_b = engine.solve(prob, mech, backend="jax",
                                    layout="bucketed", max_rounds=40)
            assert i_b.layout == "bucketed"
            np.testing.assert_allclose(a_b.x[:, order], a_d.x,
                                       atol=PARITY_ATOL)
            assert i_b.rounds == i_d.rounds

    @pytest.mark.parametrize("support", ["eligible", "full"])
    def test_batched_parity(self, x64, support):
        import jax.numpy as jnp

        from repro.core.psdsf_jax import batch_problems, psdsf_solve_batched
        probs = [sparse_cell_instance(num_users=200, num_servers=32,
                                      density=0.08, cells=4, seed=s)[0]
                 for s in (0, 1)]
        bat = batch_problems(probs, dtype=np.float64)
        d, c, w, g = (bat["demands"], bat["capacities"], bat["weights"],
                      bat["gamma"])
        full = support == "full"
        lays = [BucketedLayout.from_support(
                    np.ones(g.shape[1:]) if full else np.asarray(g[j]) > 0)
                for j in range(2)]
        bmax = max(lay.bucket_max for lay in lays)
        idx = np.stack([np.pad(lay.indices,
                               ((0, 0), (0, bmax - lay.bucket_max)))
                        for lay in lays])
        mask = np.stack([np.pad(lay.mask,
                                ((0, 0), (0, bmax - lay.bucket_max)))
                         for lay in lays])
        xb, rb, _ = psdsf_solve_batched(
            d, c, w, g, max_rounds=30,
            buckets=(jnp.asarray(idx), jnp.asarray(mask)))
        xd, rd, _ = psdsf_solve_batched(d, c, w, g, max_rounds=30)
        if full:     # buckets=None IS the full layout, bit for bit
            np.testing.assert_array_equal(np.asarray(xb), np.asarray(xd))
        else:
            np.testing.assert_allclose(np.asarray(xb), np.asarray(xd),
                                       atol=PARITY_ATOL)
        np.testing.assert_array_equal(np.asarray(rb), np.asarray(rd))

    @pytest.mark.parametrize("support", ["eligible", "full"])
    def test_resolve_batched_parity(self, x64, support):
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
        full = support == "full"
        lays = [BucketedLayout.from_support(
                    np.ones(g.shape[1:]) if full else np.asarray(g[j]) > 0)
                for j in range(2)]
        bmax = max(lay.bucket_max for lay in lays)
        idx = np.stack([np.pad(lay.indices,
                               ((0, 0), (0, bmax - lay.bucket_max)))
                        for lay in lays])
        mask = np.stack([np.pad(lay.mask,
                                ((0, 0), (0, bmax - lay.bucket_max)))
                         for lay in lays])
        xb, _, rb, resb = psdsf_resolve_batched(
            d, c, w, g, x0, srv, max_rounds=30,
            buckets=(jnp.asarray(idx), jnp.asarray(mask)))
        xd, _, rd, resd = psdsf_resolve_batched(d, c, w, g, x0, srv,
                                                max_rounds=30)
        if full:     # buckets=None IS the full layout, bit for bit
            np.testing.assert_array_equal(np.asarray(xb), np.asarray(xd))
        else:
            np.testing.assert_allclose(np.asarray(xb), np.asarray(xd),
                                       atol=PARITY_ATOL)
        np.testing.assert_allclose(np.asarray(resb), np.asarray(resd),
                                   atol=1e-12)


class TestDistributedParity:
    @pytest.mark.parametrize("eng", ["numpy", "jax", "jax-full-layout"])
    def test_tick_parity_with_churn(self, eng):
        prob, _, _ = cell_cluster_instance(num_users=128, num_servers=32,
                                           cells=8, seed=2)
        from repro.core.dynamic import DistributedPSDSF
        full = eng == "jax-full-layout"
        eng = eng.removesuffix("-full-layout")
        d_d = DistributedPSDSF(prob, engine=eng, layout="dense")
        if full:
            # the dense tick passes buckets=None: the full layout
            d_b = DistributedPSDSF(prob, engine=eng, layout="dense")
            d_b._buckets_j = _host_full_layout(prob.num_users,
                                               prob.num_servers)
        else:
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
        if full:
            np.testing.assert_array_equal(d_b.x, d_d.x)
        else:
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
        # the dense simulator sweeps the servers in the class-major order
        # of the bucketed one's colouring (the rebuild keeps it)
        perm, order = _in_class_major_order(
            prob, (prob.eligibility > 0) & act[:, None])
        at = np.argsort(order)
        evs_d = evs[:3] + [ChurnEvent(4.0, "degrade", server=int(at[2]),
                                      scale=0.5)]
        sd = ChurnSimulator(perm, initial_active=act.copy(),
                            layout="dense", max_rounds=200)
        sb = ChurnSimulator(prob, initial_active=act.copy(),
                            layout="bucketed", max_rounds=200)
        colours = sb._blayout.colours
        rd, rb = sd.run(evs_d), sb.run(evs)
        np.testing.assert_array_equal(sb._blayout.colours, colours)
        assert [r.rounds for r in rb] == [r.rounds for r in rd]
        assert rb[0].layout == "bucketed" and rb[0].bucket_max > 0
        assert [r.layout_rebuilds for r in rb] == [0, 0, 1, 0]
        assert sb.layout_rebuilds == 1
        scale = max(float(np.abs(sd.x).max()), 1.0)
        assert float(np.abs(sb.x[:, order] - sd.x).max()) <= 1e-5 * scale
