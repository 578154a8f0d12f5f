"""Golden-parity tests for the sort-free bisection fill engine (ISSUE 7).

``fill="bisect"`` must reproduce the argsort+event engine's fixed point
exactly — not approximately — because the bisection brackets every
saturation event down to a breakpoint-free segment and finishes with the
exact closed-form segment root. The suite pins that contract across every
implementation layer:

  * numpy ``server_fill_*_bisect`` vs the event oracle (per-server, and
    through ``solve_psdsf_rdm/tdm``) on the Section II-B examples and the
    pinned dense instance;
  * the jitted jax engine (f64 and the f32 ``precision="fast"`` path, each
    with its own pinned tolerance) plus the batched solver;
  * the Pallas ``psdsf_fill`` kernel in interpret mode at the dense fixed
    point (the kernel-vs-oracle sweep lives in
    ``tests/test_kernels_interpret.py``);
  * the opt-in damped-Jacobi round mode (regression-pinned on the 100x20
    instance: converged, and on the Gauss-Seidel fixed point);
  * the observability satellite: ``SolveInfo.fill_engine/fill_iters`` and
    ``ChurnRecord.fill_engine/fill_iters`` report the engine that ran and
    its inner-iteration budget;
  * validation: unknown engines, numpy-backend ``round="jacobi"``, and
    fill/round on closed-form mechanisms all raise.
"""
import numpy as np
import pytest

from repro.core import (DistributedPSDSF, gamma_matrix, solve,
                        solve_psdsf_rdm, solve_psdsf_tdm)
from repro.core.instances import (cell_cluster_instance,
                                  dense_random_instance, fig1_instance,
                                  fig2_instance)
from repro.core.placement import (FILL_ENGINES, fill_iter_budget,
                                  server_fill_rdm, server_fill_rdm_bisect,
                                  server_fill_tdm, server_fill_tdm_bisect)

from conftest import random_problems

#: event-vs-bisect parity on converged/pinned fixed points (the ISSUE-7
#: acceptance bar; the engines actually agree to ~1e-14)
PARITY_ATOL = 1e-9
#: Section II-B worked examples (three-user / four-user, exact arithmetic)
PAPER_ATOL = 1e-6
#: the f32 ``precision="fast"`` jitted path (measured ~3e-6 on dense)
F32_ATOL = 5e-5


def _jax_solve(prob, mode="rdm", dtype=None, **kw):
    import jax.numpy as jnp

    from repro.core.psdsf_jax import psdsf_solve_jax
    dt = jnp.float64 if dtype is None else dtype
    g = gamma_matrix(prob)
    kw.setdefault("max_rounds", 128)
    return psdsf_solve_jax(
        jnp.asarray(prob.demands, dt), jnp.asarray(prob.capacities, dt),
        jnp.asarray(prob.weights, dt), jnp.asarray(g, dt), mode=mode, **kw)


# function-scoped on purpose: a module-scoped context would stay active
# across the f32 ``precision="fast"`` test below and silently promote its
# internal constants to f64
@pytest.fixture()
def x64():
    import jax
    with jax.enable_x64(True):
        yield


class TestNumpyParity:
    @pytest.mark.parametrize("prob_fn", [fig1_instance, fig2_instance])
    @pytest.mark.parametrize("solver", [solve_psdsf_rdm, solve_psdsf_tdm])
    def test_section_iib_examples(self, prob_fn, solver):
        prob = prob_fn()
        a_ev, i_ev = solver(prob, fill="event")
        a_bi, i_bi = solver(prob, fill="bisect")
        assert i_ev.converged and i_bi.converged
        np.testing.assert_allclose(a_bi.x, a_ev.x, atol=PAPER_ATOL)

    def test_fig1_paper_values_via_bisect(self):
        alloc, _ = solve_psdsf_rdm(fig1_instance(), fill="bisect")
        np.testing.assert_allclose(alloc.tasks_per_user, [3.0, 3.0, 6.0],
                                   atol=1e-3)

    def test_pinned_dense_fixed_point(self):
        prob = dense_random_instance()
        a_ev, _ = solve_psdsf_rdm(prob, max_rounds=128, tol=1e-6)
        a_bi, _ = solve_psdsf_rdm(prob, max_rounds=128, tol=1e-6,
                                  fill="bisect")
        assert float(np.abs(a_bi.x - a_ev.x).max()) <= PARITY_ATOL

    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    def test_per_server_fill_random_external_floors(self, mode):
        rng = np.random.default_rng(7)
        for prob in random_problems(6, seed=3):
            g = gamma_matrix(prob)
            x_ext = rng.uniform(0.0, 3.0, prob.num_users)
            for i in range(prob.num_servers):
                if mode == "rdm":
                    ev = server_fill_rdm(prob.capacities[i], prob.demands,
                                         prob.weights, g[:, i], x_ext)
                    bi = server_fill_rdm_bisect(prob.capacities[i],
                                                prob.demands, prob.weights,
                                                g[:, i], x_ext)
                else:
                    ev = server_fill_tdm(prob.demands, prob.weights, g[:, i],
                                         x_ext)
                    bi = server_fill_tdm_bisect(prob.demands, prob.weights,
                                                g[:, i], x_ext)
                np.testing.assert_allclose(bi, ev, atol=1e-8)


class TestJaxParity:
    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    def test_random_instances_f64(self, x64, mode):
        for prob in random_problems(4, seed=11):
            x_ev, r_ev, _ = _jax_solve(prob, mode=mode, fill="event")
            x_bi, r_bi, _ = _jax_solve(prob, mode=mode, fill="bisect")
            assert int(r_ev) == int(r_bi)
            assert float(np.abs(np.asarray(x_bi) -
                                np.asarray(x_ev)).max()) <= PARITY_ATOL

    def test_pinned_dense_f64(self, x64):
        prob = dense_random_instance()
        x_ev, _, _ = _jax_solve(prob, fill="event", tol=1e-6)
        x_bi, _, _ = _jax_solve(prob, fill="bisect", tol=1e-6)
        assert float(np.abs(np.asarray(x_bi) -
                            np.asarray(x_ev)).max()) <= PARITY_ATOL

    def test_pinned_cell_f64(self, x64):
        cell, _, _ = cell_cluster_instance(num_users=256, num_servers=32,
                                           cells=4, seed=0)
        x_ev, _, _ = _jax_solve(cell, fill="event", max_rounds=64, tol=1e-6)
        x_bi, _, _ = _jax_solve(cell, fill="bisect", max_rounds=64, tol=1e-6)
        assert float(np.abs(np.asarray(x_bi) -
                            np.asarray(x_ev)).max()) <= PARITY_ATOL

    def test_precision_fast_f32_tolerance_pinned(self):
        import jax.numpy as jnp
        prob = dense_random_instance()
        x_ev, _, _ = _jax_solve(prob, dtype=jnp.float32, fill="event",
                                tol=1e-6)
        x_bi, _, _ = _jax_solve(prob, dtype=jnp.float32, fill="bisect",
                                tol=1e-6)
        scale = float(prob.capacities.max())
        assert (float(np.abs(np.asarray(x_bi, np.float64) -
                             np.asarray(x_ev, np.float64)).max())
                <= F32_ATOL * scale)

    def test_batched_f64(self, x64):
        from repro.core.psdsf_jax import batch_problems, psdsf_solve_batched
        b = batch_problems(random_problems(5, seed=19), dtype=np.float64)
        out_ev = psdsf_solve_batched(b["demands"], b["capacities"],
                                     b["weights"], b["gamma"],
                                     max_rounds=64, fill="event")
        out_bi = psdsf_solve_batched(b["demands"], b["capacities"],
                                     b["weights"], b["gamma"],
                                     max_rounds=64, fill="bisect")
        assert float(np.abs(np.asarray(out_bi[0]) -
                            np.asarray(out_ev[0])).max()) <= PARITY_ATOL

    def test_distributed_ticks_match(self, x64):
        prob = dense_random_instance()
        sims = {fill: DistributedPSDSF(prob, engine="jax", fill=fill)
                for fill in FILL_ENGINES}
        for _ in range(6):
            for sim in sims.values():
                sim.tick()
        assert float(np.abs(sims["bisect"].x -
                            sims["event"].x).max()) <= PARITY_ATOL


class TestPallasFixedPoint:
    def test_dense_fixed_point_interpret(self, x64):
        # the dense instance limit-cycles (its residual floors at ~1.5e-3),
        # so re-filling at the last iterate is NOT the identity there — the
        # 1e-9 pin is kernel-vs-event-oracle parity at that pinned state;
        # the identity-at-equilibrium check runs on a converging instance
        # in tests/test_kernels_interpret.py
        from repro.kernels.psdsf_fill.ops import fill_cluster_padded
        from repro.kernels.psdsf_fill.ref import fill_cluster_ref
        prob = dense_random_instance()
        alloc, _ = solve_psdsf_rdm(prob, max_rounds=128, tol=1e-6)
        g = gamma_matrix(prob)
        x_ext = alloc.x.sum(axis=1, keepdims=True) - alloc.x
        got = fill_cluster_padded(prob.capacities, prob.demands,
                                  prob.weights, g, x_ext, mode="rdm",
                                  interpret=True)
        want = fill_cluster_ref(prob.capacities, prob.demands, prob.weights,
                                g, x_ext, mode="rdm")
        assert float(np.abs(got - want).max()) <= PARITY_ATOL


class TestJacobiRound:
    def test_jacobi_converges_on_paper_examples(self, x64):
        # where Gauss-Seidel converges, damped Jacobi must converge too and
        # land on the SAME fixed point (slower — that is the trade; the
        # round exists for the cluster-wide Pallas fill, not CPU speed)
        for prob_fn in (fig1_instance, fig2_instance):
            prob = prob_fn()
            x_g, _, _ = _jax_solve(prob, fill="bisect", round="gauss",
                                   max_rounds=512, tol=1e-8)
            x_j, r_j, _ = _jax_solve(prob, fill="bisect", round="jacobi",
                                     max_rounds=512, tol=1e-8)
            assert int(r_j) < 512                # converged, not capped
            assert (float(np.abs(np.asarray(x_j) -
                                 np.asarray(x_g)).max()) <= 1e-6)

    def test_jacobi_regression_pin_100x20(self, x64):
        # the allocator_scaling instance recipe, pinned: this contended
        # instance limit-cycles for BOTH outer rounds at tol=1e-6 (gauss
        # floors at ~3.5e-5 * scale, jacobi at ~1.3e-4 * scale) — the pin
        # is that jacobi's cycle amplitude stays within ~4x of gauss's and
        # the aggregate allocation agrees to ~1.5% (measured values; a
        # looser future run means the damping schedule regressed)
        rng = np.random.default_rng(0)
        n, k = 100, 20
        from repro.core import AllocationProblem
        prob = AllocationProblem(rng.uniform(0.05, 2.0, (n, 4)),
                                 rng.uniform(5.0, 50.0, (k, 4)),
                                 rng.uniform(0.5, 2.0, n),
                                 (rng.random((n, k)) > 0.3).astype(float))
        x_g, _, res_g = _jax_solve(prob, fill="bisect", round="gauss",
                                   max_rounds=256, tol=1e-6)
        x_j, _, res_j = _jax_solve(prob, fill="bisect", round="jacobi",
                                   max_rounds=256, tol=1e-6)
        scale = float(gamma_matrix(prob).max())
        assert float(res_g) <= 5e-5 * scale
        assert float(res_j) <= 2e-4 * scale
        t_g = float(np.asarray(x_g).sum())
        t_j = float(np.asarray(x_j).sum())
        assert abs(t_j - t_g) / t_g <= 0.02

    def test_numpy_backend_rejects_jacobi(self):
        with pytest.raises(ValueError, match="jax"):
            solve(fig1_instance(), mechanism="psdsf-rdm", backend="numpy",
                  round="jacobi")

    def test_closed_form_rejects_fill_axis(self):
        for kw in ({"fill": "bisect"}, {"round": "jacobi"}):
            with pytest.raises(ValueError, match="closed-form"):
                solve(fig1_instance(), mechanism="drf", **kw)

    def test_unknown_fill_engine_rejected(self):
        with pytest.raises(ValueError, match="fill"):
            solve_psdsf_rdm(fig1_instance(), fill="newton")
        with pytest.raises(ValueError, match="fill"):
            DistributedPSDSF(fig1_instance(), fill="newton")


class TestObservability:
    def test_solveinfo_numpy(self):
        prob = fig1_instance()
        for fill in FILL_ENGINES:
            _, info = solve_psdsf_rdm(prob, fill=fill)
            assert info.fill_engine == fill
            budget = fill_iter_budget(prob.num_resources, "rdm", fill)
            assert info.fill_iters > 0
            assert info.fill_iters % budget == 0

    def test_solveinfo_jax(self):
        prob = fig1_instance()
        _, info = solve(prob, mechanism="psdsf-rdm", backend="jax",
                        fill="bisect")
        assert info.fill_engine == "bisect"
        assert info.fill_iters == (info.rounds * prob.num_servers *
                                   fill_iter_budget(prob.num_resources,
                                                    "rdm", "bisect"))

    def test_churn_record_carries_fill_fields(self):
        from repro.sched.churn import ChurnSimulator
        prob = dense_random_instance()
        sim = ChurnSimulator(prob, fill="bisect", max_rounds=32, tol=1e-4,
                             telemetry=False)
        rec = sim.step([], 0.0)
        assert rec.fill_engine == "bisect"
        assert rec.fill_iters == (rec.rounds * prob.num_servers *
                                  fill_iter_budget(prob.num_resources,
                                                   "rdm", "bisect"))
        with pytest.raises(ValueError, match="fill"):
            ChurnSimulator(prob, fill="newton")


# a module-level importorskip would skip the whole parity suite on boxes
# without hypothesis; only the property test itself may skip
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                        # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    class TestPropertyParity:
        @settings(max_examples=25, deadline=None)
        @given(seed=st.integers(0, 2**31 - 1), mode=st.sampled_from(
            ["rdm", "tdm"]))
        def test_per_server_event_bisect_agree(self, seed, mode):
            prob = random_problems(1, seed=seed)[0]
            rng = np.random.default_rng(seed)
            g = gamma_matrix(prob)
            x_ext = rng.uniform(0.0, 4.0, prob.num_users)
            for i in range(prob.num_servers):
                if mode == "rdm":
                    ev = server_fill_rdm(prob.capacities[i], prob.demands,
                                         prob.weights, g[:, i], x_ext)
                    bi = server_fill_rdm_bisect(prob.capacities[i],
                                                prob.demands, prob.weights,
                                                g[:, i], x_ext)
                else:
                    ev = server_fill_tdm(prob.demands, prob.weights, g[:, i],
                                         x_ext)
                    bi = server_fill_tdm_bisect(prob.demands, prob.weights,
                                                g[:, i], x_ext)
                np.testing.assert_allclose(bi, ev, atol=1e-8)
else:
    @pytest.mark.skip(reason="the fill-parity property test needs "
                      "hypothesis (pip install -e .[test]); the CI fast "
                      "lane installs it")
    def test_per_server_event_bisect_agree_property():
        pass                                               # pragma: no cover
