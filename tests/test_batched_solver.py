"""Golden-parity tests for the batched / warm-started solver engine.

Covers the contracts the engine is built on:
  * ``psdsf_solve_batched`` == per-problem ``psdsf_solve_jax`` (RDM + TDM),
    including zero-padding of heterogeneous problems;
  * warm starts reach the same fixed point in fewer rounds;
  * ``DistributedPSDSF(engine="jax")`` ticks match the numpy oracle engine;
  * the Pallas VDS reduction behind ``min_vds`` matches its jnp oracle;
  * the churn simulator's warm re-solves land on the direct solver's fixed
    point (per-user totals — the paper-unique quantity; the split across
    identical servers is not unique);
  * ``psdsf_resolve_batched`` (restricted sweep + verification) certifies
    scenarios at the same tolerance as cold solves.
"""
import functools

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import AllocationProblem, DistributedPSDSF, gamma_matrix
from repro.core.instances import (cell_cluster_instance, fault_scenarios,
                                  fig1_instance, fig2_instance,
                                  google_cluster_instance)
from repro.core.psdsf_jax import (batch_problems, psdsf_resolve_batched,
                                  psdsf_solve_batched, psdsf_solve_jax,
                                  unbatch_solutions)

from conftest import random_problems as _random_problems

#: this suite historically draws slightly larger instances (the batching
#: padding paths need heterogeneous N/K) — same shared generator, bigger
#: defaults
random_problems = functools.partial(_random_problems, max_users=10,
                                    max_servers=5, max_resources=4)


def solve_one(prob, mode, x0=None, max_rounds=64):
    g = jnp.asarray(gamma_matrix(prob), jnp.float32)
    return psdsf_solve_jax(
        jnp.asarray(prob.demands, jnp.float32),
        jnp.asarray(prob.capacities, jnp.float32),
        jnp.asarray(prob.weights, jnp.float32), g,
        x0=None if x0 is None else jnp.asarray(x0, jnp.float32),
        mode=mode, max_rounds=max_rounds)


class TestBatchedParity:
    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    def test_batched_matches_per_problem(self, mode):
        probs = random_problems(6, seed=3)
        bat = batch_problems(probs)
        xb, rounds, resid = psdsf_solve_batched(
            bat["demands"], bat["capacities"], bat["weights"], bat["gamma"],
            mode=mode, max_rounds=64)
        allocs = unbatch_solutions(xb, probs)
        for j, prob in enumerate(probs):
            x1, r1, _ = solve_one(prob, mode)
            np.testing.assert_allclose(allocs[j].x, np.asarray(x1),
                                       atol=1e-5)
            assert int(rounds[j]) == int(r1), "padding changed the trajectory"

    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    def test_padding_is_inert(self, mode):
        """A problem solved alone and inside a ragged batch agrees exactly."""
        probs = random_problems(4, seed=11, max_users=12, max_servers=6)
        bat = batch_problems(probs)
        xb, _, _ = psdsf_solve_batched(
            bat["demands"], bat["capacities"], bat["weights"], bat["gamma"],
            mode=mode, max_rounds=64)
        for j, prob in enumerate(probs):
            n, k = prob.num_users, prob.num_servers
            pad = np.asarray(xb[j])
            assert np.all(pad[n:, :] == 0), "padded users got tasks"
            assert np.all(pad[:, k:] == 0), "padded servers got tasks"


class TestWarmStart:
    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    def test_warm_from_fixed_point_is_one_round(self, mode):
        converged = 0
        for prob in random_problems(4, seed=5):
            x_cold, r_cold, res_cold = solve_one(prob, mode)
            x_warm, r_warm, res_warm = solve_one(prob, mode,
                                                 x0=np.asarray(x_cold))
            if int(r_cold) >= 64:
                # cold never converged (limit cycle): the warm solve simply
                # continues the descent — it must not do worse
                assert float(res_warm) <= float(res_cold) * 1.01
                continue
            converged += 1
            assert int(r_warm) <= max(1, int(r_cold) // 2)
            scale = max(1.0, float(np.abs(np.asarray(x_cold)).max()))
            # exactly-converged instances restart to themselves; instances
            # in a damped limit cycle stay within the residual band
            atol = max(1e-4, 30.0 * float(res_cold) / scale)
            np.testing.assert_allclose(np.asarray(x_warm) / scale,
                                       np.asarray(x_cold) / scale, atol=atol)
        assert converged >= 2, "test instances too degenerate"

    def test_warm_after_small_perturbation_saves_rounds(self):
        prob = google_cluster_instance()[0]
        x_cold, r_cold, _ = solve_one(prob, "rdm")
        # user 3 departs: warm-start the shrunken problem from the old point
        elig = prob.eligibility.copy()
        elig[3] = 0.0
        pert = AllocationProblem(prob.demands, prob.capacities,
                                 prob.weights, elig)
        x0 = np.asarray(x_cold).copy()
        x0[3] = 0.0
        x_warm, r_warm, _ = solve_one(pert, "rdm", x0=x0)
        x_pert_cold, r_pert_cold, _ = solve_one(pert, "rdm")
        assert int(r_warm) <= int(r_pert_cold)
        np.testing.assert_allclose(np.asarray(x_warm).sum(axis=1),
                                   np.asarray(x_pert_cold).sum(axis=1),
                                   atol=1e-3)


class TestEngineParity:
    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    @pytest.mark.parametrize("prob_fn,name", [
        (fig1_instance, "fig1"), (fig2_instance, "fig2"),
        (lambda: google_cluster_instance()[0], "google")],
        ids=lambda p: p if isinstance(p, str) else "")
    def test_jax_engine_matches_numpy(self, mode, prob_fn, name):
        prob = prob_fn()
        a = DistributedPSDSF(prob, mode=mode, engine="numpy")
        b = DistributedPSDSF(prob, mode=mode, engine="jax")
        for _ in range(5):
            a.tick()
            b.tick()
        np.testing.assert_allclose(b.x, a.x, atol=1e-5)
        # churn + subset + shuffled order (same seed -> same permutation)
        a.set_active(prob.num_users - 1, False)
        b.set_active(prob.num_users - 1, False)
        sub = range(0, prob.num_servers, 2)
        a.tick(servers=sub, shuffle=True)
        b.tick(servers=sub, shuffle=True)
        np.testing.assert_allclose(b.x, a.x, atol=1e-5)

    def test_min_vds_matches_oracle(self):
        from repro.kernels.psdsf_vds.ref import vds_argmin_ref
        prob = google_cluster_instance()[0]
        sim = DistributedPSDSF(prob, engine="jax")
        sim.tick()
        mn, arg = sim.min_vds()
        g = np.where(sim.active[:, None], sim.gamma, 0.0)
        ref_mn, ref_arg = vds_argmin_ref(
            jnp.asarray(sim.x.sum(axis=1) / prob.weights, jnp.float32),
            jnp.asarray(g, jnp.float32))
        np.testing.assert_allclose(mn, np.asarray(ref_mn), rtol=1e-6)
        np.testing.assert_array_equal(arg, np.asarray(ref_arg))


class TestChurnSimulator:
    def test_section_v_roundtrip(self):
        from repro.sched.churn import ChurnEvent, ChurnSimulator
        prob = google_cluster_instance()[0]
        sim = ChurnSimulator(prob, compare_cold=True, telemetry=True)
        sim.step([], 0.0)
        recs = sim.run([ChurnEvent(100.0, "departure", user=3),
                        ChurnEvent(250.0, "arrival", user=3)])
        assert [r.active_users for r in recs] == [3, 4]
        # after the arrival the warm re-solve must land back on the full
        # problem's fixed point (per-user totals are the unique quantity)
        x_ref, _, _ = solve_one(prob, "rdm")
        np.testing.assert_allclose(sim.x.sum(axis=1),
                                   np.asarray(x_ref).sum(axis=1), atol=1e-3)
        for r in recs:
            assert r.rounds <= max(1, r.cold_rounds)
            assert np.isfinite(r.min_vds)

    def test_degrade_restore(self):
        from repro.sched.churn import ChurnEvent, ChurnSimulator
        prob, _, _ = cell_cluster_instance(num_users=48, num_servers=8,
                                           cells=2, seed=7)
        sim = ChurnSimulator(prob, telemetry=False, max_rounds=64, tol=1e-4)
        rec0 = sim.step([], 0.0)
        x_before = sim.x.copy()
        recs = sim.run([ChurnEvent(1.0, "degrade", server=2, scale=0.5),
                        ChurnEvent(9.0, "restore", server=2)])
        assert recs[0].total_tasks < rec0.total_tasks + 1e-6
        # restore must land back inside the original equilibrium's cycle
        # band (the sweep's residual floor on cycling instances, ~2% of the
        # per-user total here — see the limit-cycle note in psdsf_jax)
        band = 0.1 * float(np.mean(x_before.sum(axis=1)))
        np.testing.assert_allclose(sim.x.sum(axis=1), x_before.sum(axis=1),
                                   atol=band)
        assert abs(recs[-1].total_tasks - rec0.total_tasks) < band * 4

    def test_event_validation(self):
        from repro.sched.churn import ChurnEvent
        with pytest.raises(ValueError):
            ChurnEvent(0.0, "explode", user=1)


class TestIncrementalResolve:
    def test_scenarios_certify_at_cold_tolerance(self):
        base, home, is_cross = cell_cluster_instance(
            num_users=96, num_servers=16, cells=4, seed=2)
        g = gamma_matrix(base)
        tol = 1e-4
        x_base, _, _ = solve_one(base, "rdm")
        scen = fault_scenarios(base, home, is_cross, num_scenarios=4,
                               cells=4, departed_users=4, seed=3)
        b = len(scen)
        s_max = max(len(s["affected_servers"]) for s in scen)
        dsb = jnp.broadcast_to(jnp.asarray(base.demands, jnp.float32),
                               (b,) + base.demands.shape)
        wsb = jnp.broadcast_to(jnp.asarray(base.weights, jnp.float32),
                               (b, base.num_users))
        csb = jnp.asarray(np.stack([s["problem"].capacities for s in scen]),
                          jnp.float32)
        gsb = jnp.asarray(np.stack([gamma_matrix(s["problem"])
                                    for s in scen]), jnp.float32)
        x0s = []
        for s in scen:
            x0 = np.asarray(x_base, np.float64).copy()
            x0[s["departed_users"]] = 0.0
            x0s.append(x0)
        x0b = jnp.asarray(np.stack(x0s), jnp.float32)
        srv = jnp.asarray(np.stack(
            [np.resize(s["affected_servers"], s_max) for s in scen]))
        xw, rr, rf, resid = psdsf_resolve_batched(
            dsb, csb, wsb, gsb, x0b, srv, max_rounds=64, tol=tol)
        scale = float(np.asarray(gsb).max())
        # the certificate: every scenario's full-sweep residual passes the
        # same tolerance a cold solve accepts at
        assert float(np.asarray(resid).max()) <= tol * scale * 1.01
        # and the solutions agree with cold solves within the sweep's
        # limit-cycle band (both are equally-certified members of it)
        for j, s in enumerate(scen):
            x_cold, _, _ = solve_one(s["problem"], "rdm")
            tots_cold = np.asarray(x_cold).sum(axis=1)
            tots_warm = np.asarray(xw[j]).sum(axis=1)
            xscale = max(1.0, tots_cold.max())
            np.testing.assert_allclose(tots_warm / xscale,
                                       tots_cold / xscale, atol=0.1)
