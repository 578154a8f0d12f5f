"""Interpret-mode lane for the scheduler Pallas kernels: ``psdsf_vds``,
``psdsf_fill``, ``psdsf_fill_bucketed`` and their compiler params, all
runnable on a CPU-only box (``JAX_PLATFORMS=cpu``) — this file IS the CI
"kernels (interpret)" step, so it must stay importable and green with no
TPU anywhere. The compiled lowering of the same kernels is checked by
``tests/test_tpu_compile.py``.

The deep fill-engine parity suite lives in ``tests/test_fill_bisect.py``;
here each kernel is exercised against its independent oracle through the
``interpret=True`` path specifically (grid/BlockSpec/scratch plumbing, the
padded-layout wrappers, and dtype genericity under ``enable_x64``).
"""
import numpy as np
import pytest

from repro.core import gamma_matrix, solve_psdsf_rdm
from repro.core.instances import (dense_random_instance, fig1_instance,
                                  fig2_instance)

from conftest import random_problems


# function-scoped: a module-scoped context would leak f64 into the f32
# tolerance test below
@pytest.fixture()
def x64():
    import jax
    with jax.enable_x64(True):
        yield


class TestCompilerParams:
    def test_compiler_params_resolves(self):
        from jax.experimental.pallas import tpu as pltpu
        assert not hasattr(pltpu, "TPUCompilerParams")
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        assert params.dimension_semantics == ("parallel", "arbitrary")

    def test_all_kernels_use_pltpu_compiler_params(self):
        # every scheduler kernel passes pltpu.CompilerParams directly and
        # never names the pre-rename TPUCompilerParams, which the supported
        # jax line no longer ships
        import ast
        import inspect

        from repro.kernels.psdsf_fill import kernel as fill_kernel
        from repro.kernels.psdsf_fill_bucketed import kernel as bfill_kernel
        from repro.kernels.psdsf_vds import kernel as vds_kernel
        for mod in (vds_kernel, fill_kernel, bfill_kernel):
            tree = ast.parse(inspect.getsource(mod))
            names = {n.attr for n in ast.walk(tree)
                     if isinstance(n, ast.Attribute)}
            assert "TPUCompilerParams" not in names, mod.__name__
            assert "CompilerParams" in names, mod.__name__
            assert "_compat" not in {n.id for n in ast.walk(tree)
                                     if isinstance(n, ast.Name)}


class TestPsdsfVds:
    def test_vds_argmin_matches_ref(self):
        from repro.kernels.psdsf_vds.kernel import vds_argmin
        from repro.kernels.psdsf_vds.ref import vds_argmin_ref
        rng = np.random.default_rng(5)
        x_over_phi = rng.uniform(0.0, 10.0, 96).astype(np.float32)
        gamma = (rng.uniform(0.0, 2.0, (96, 24)) *
                 (rng.random((96, 24)) > 0.4)).astype(np.float32)
        got_mn, got_arg = vds_argmin(x_over_phi, gamma, interpret=True)
        ref_mn, ref_arg = vds_argmin_ref(x_over_phi, gamma)
        np.testing.assert_allclose(np.asarray(got_mn), np.asarray(ref_mn),
                                   rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(got_arg),
                                      np.asarray(ref_arg))


class TestPsdsfFill:
    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    @pytest.mark.parametrize("prob_fn", [fig1_instance, fig2_instance,
                                         dense_random_instance])
    def test_cluster_fill_matches_oracle_f64(self, x64, mode, prob_fn):
        from repro.kernels.psdsf_fill.ops import fill_cluster_padded
        from repro.kernels.psdsf_fill.ref import fill_cluster_ref
        prob = prob_fn()
        g = gamma_matrix(prob)
        rng = np.random.default_rng(9)
        x_ext = rng.uniform(0.0, 2.0, (prob.num_users, prob.num_servers))
        got = fill_cluster_padded(prob.capacities, prob.demands,
                                  prob.weights, g, x_ext, mode=mode,
                                  interpret=True)
        want = fill_cluster_ref(prob.capacities, prob.demands, prob.weights,
                                g, x_ext, mode=mode)
        np.testing.assert_allclose(got, want, atol=1e-9)

    def test_cluster_fill_random_instances_f64(self, x64):
        from repro.kernels.psdsf_fill.ops import fill_cluster_padded
        from repro.kernels.psdsf_fill.ref import fill_cluster_ref
        rng = np.random.default_rng(21)
        for prob in random_problems(4, seed=13):
            g = gamma_matrix(prob)
            x_ext = rng.uniform(0.0, 3.0,
                                (prob.num_users, prob.num_servers))
            got = fill_cluster_padded(prob.capacities, prob.demands,
                                      prob.weights, g, x_ext, mode="rdm",
                                      interpret=True)
            want = fill_cluster_ref(prob.capacities, prob.demands,
                                    prob.weights, g, x_ext, mode="rdm")
            np.testing.assert_allclose(got, want, atol=1e-9)

    def test_cluster_fill_f32_tolerance_pinned(self):
        # without enable_x64 the kernel runs in f32 with the shorter
        # bisection-step cap — parity loosens to ~1e-7 RELATIVE (9.7e-8
        # measured on the cell instance); pin the f32 contract here
        from repro.core.instances import cell_cluster_instance
        from repro.kernels.psdsf_fill.ops import fill_cluster_padded
        from repro.kernels.psdsf_fill.ref import fill_cluster_ref
        cell, _, _ = cell_cluster_instance(num_users=256, num_servers=32,
                                           cells=4, seed=0)
        g = gamma_matrix(cell)
        rng = np.random.default_rng(2)
        x_ext = rng.uniform(0.0, 2.0, (cell.num_users, cell.num_servers))
        got = fill_cluster_padded(cell.capacities, cell.demands,
                                  cell.weights, g, x_ext, mode="rdm",
                                  interpret=True)
        want = fill_cluster_ref(cell.capacities, cell.demands, cell.weights,
                                g, x_ext, mode="rdm")
        scale = max(float(np.abs(want).max()), 1.0)
        assert float(np.abs(got - want).max()) <= 5e-6 * scale

class TestPsdsfFillBucketed:
    @staticmethod
    def _gathered(prob, g, x_ext):
        from repro.core.layout import BucketedLayout
        lay = BucketedLayout.from_support(g > 0)
        idx, mask = lay.indices, lay.mask
        gam_b = np.where(mask, np.take_along_axis(g.T, idx, axis=1), 0.0)
        xeb = np.where(mask, np.take_along_axis(x_ext.T, idx, axis=1), 0.0)
        return lay, prob.demands[idx], prob.weights[idx], gam_b, xeb, mask

    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    @pytest.mark.parametrize("prob_fn", [fig1_instance, fig2_instance,
                                         dense_random_instance])
    def test_bucketed_fill_matches_oracle_f64(self, x64, mode, prob_fn):
        from repro.kernels.psdsf_fill_bucketed.ops import \
            fill_cluster_bucketed_padded
        from repro.kernels.psdsf_fill_bucketed.ref import \
            fill_cluster_bucketed_ref
        prob = prob_fn()
        g = gamma_matrix(prob)
        rng = np.random.default_rng(9)
        x_ext = rng.uniform(0.0, 2.0, (prob.num_users, prob.num_servers))
        _, dem_b, phi_b, gam_b, xeb, mask = self._gathered(prob, g, x_ext)
        got = fill_cluster_bucketed_padded(prob.capacities, dem_b, phi_b,
                                           gam_b, xeb, mask, mode=mode,
                                           interpret=True)
        want = fill_cluster_bucketed_ref(prob.capacities, dem_b, phi_b,
                                         gam_b, xeb, mask, mode=mode)
        np.testing.assert_allclose(got, want, atol=1e-9)

    @pytest.mark.parametrize("mode", ["rdm", "tdm"])
    def test_bucketed_fill_matches_dense_kernel_f64(self, x64, mode):
        # the two kernels must agree at the DENSE fixed-point contract,
        # not just each against its own oracle: scatter the bucketed fill
        # and compare to the dense kernel on a sparse cell instance
        from repro.core.instances import sparse_cell_instance
        from repro.kernels.psdsf_fill.ops import fill_cluster_padded
        from repro.kernels.psdsf_fill_bucketed.ops import \
            fill_cluster_bucketed_padded
        prob, _ = sparse_cell_instance(num_users=200, num_servers=32,
                                       density=0.1, cells=4, seed=3)
        g = gamma_matrix(prob)
        rng = np.random.default_rng(4)
        x_ext = rng.uniform(0.0, 2.0, (prob.num_users, prob.num_servers))
        lay, dem_b, phi_b, gam_b, xeb, mask = self._gathered(prob, g, x_ext)
        got = fill_cluster_bucketed_padded(prob.capacities, dem_b, phi_b,
                                           gam_b, xeb, mask, mode=mode,
                                           interpret=True)
        dense = fill_cluster_padded(prob.capacities, prob.demands,
                                    prob.weights, g, x_ext, mode=mode,
                                    interpret=True)
        np.testing.assert_allclose(lay.scatter(got), dense, atol=1e-9)

    def test_degenerate_buckets(self, x64):
        # an empty server bucket and a user eligible nowhere must both be
        # inert; density=1 buckets must reproduce the dense oracle
        from repro.kernels.psdsf_fill_bucketed.ops import \
            fill_cluster_bucketed_padded
        from repro.kernels.psdsf_fill_bucketed.ref import \
            fill_cluster_bucketed_ref
        prob = dense_random_instance(num_users=24, num_servers=6)
        elig = prob.eligibility.copy()
        elig[:, 2] = 0.0                 # server 2: nobody eligible
        elig[5, :] = 0.0                 # user 5: eligible nowhere
        from repro.core.types import AllocationProblem
        prob = AllocationProblem(prob.demands, prob.capacities,
                                 prob.weights, elig)
        g = gamma_matrix(prob)
        rng = np.random.default_rng(0)
        x_ext = rng.uniform(0.0, 2.0, (prob.num_users, prob.num_servers))
        lay, dem_b, phi_b, gam_b, xeb, mask = self._gathered(prob, g, x_ext)
        got = fill_cluster_bucketed_padded(prob.capacities, dem_b, phi_b,
                                           gam_b, xeb, mask, interpret=True)
        want = fill_cluster_bucketed_ref(prob.capacities, dem_b, phi_b,
                                         gam_b, xeb, mask)
        np.testing.assert_allclose(got, want, atol=1e-9)
        assert not mask[2].any() and np.abs(got[2]).max() == 0.0
        assert lay.scatter(got)[5].max() == 0.0

    def test_fixed_point_is_invariant(self, x64):
        # one whole-cluster Jacobi fill AT the solved fixed point must be
        # the identity — ties the kernel to the solver contract, not just
        # to the oracle
        from repro.kernels.psdsf_fill.ops import fill_cluster_padded
        prob = fig2_instance()
        alloc, _ = solve_psdsf_rdm(prob)
        g = gamma_matrix(prob)
        x_ext = alloc.x.sum(axis=1, keepdims=True) - alloc.x
        got = fill_cluster_padded(prob.capacities, prob.demands,
                                  prob.weights, g, x_ext, mode="rdm",
                                  interpret=True)
        np.testing.assert_allclose(got, alloc.x, atol=1e-9)
