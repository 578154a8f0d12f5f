"""Compile the scheduler kernels and the churn re-solve for a described
TPU v5e at the pinned instance's real size, with no chip attached.

Nothing runs: each test lowers and compiles against a described ``v5e:2x2``
topology, so the TPU compiler refuses here what it would refuse on the chip
(unaligned tiles, scoped VMEM, programs that do not fit HBM). The topology
is described inside a module-scoped fixture, never at import, because only
one process may load the TPU library; keep every such compile in this one
file. The persistent compilation cache is off around the compiles: an entry
written for a described device cannot be read back without one.
"""
import inspect

import pytest

N_USERS, N_SERVERS, N_RES = 20000, 256, 4     # sparse_cell_instance()
BUCKET_MAX = 692                              # its bucketed layout's Bmax
HBM_BYTES = 16 * 10**9                        # one v5e chip


def _pad(n: int, block: int) -> int:
    return n + (-n % block)


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the system temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except RuntimeError as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype=None):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, dtype or jnp.float32,
                                sharding=one_chip)


def test_vds_argmin_compiles_to_mosaic(one_chip):
    from repro.kernels.psdsf_vds.kernel import vds_argmin

    n = _pad(N_USERS, 256)
    compiled = vds_argmin.lower(_shape(one_chip, (n,)),
                                _shape(one_chip, (n, N_SERVERS)),
                                block_n=256, block_k=128).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fill_event_levels_compiles(one_chip):
    from repro.core.placement import BISECT_STEPS_F32
    from repro.kernels.psdsf_fill.kernel import fill_event_levels

    n, k = _pad(N_USERS, 256), N_SERVERS
    compiled = fill_event_levels.lower(
        _shape(one_chip, (n, k)), _shape(one_chip, (n, k)),
        _shape(one_chip, (n, N_RES)), _shape(one_chip, (k, N_RES)),
        _shape(one_chip, (k, N_RES)), _shape(one_chip, (k, N_RES)),
        _shape(one_chip, (k,)), steps=BISECT_STEPS_F32).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(
    raises=RuntimeError, strict=True,
    reason="TPU compiler: RESOURCE_EXHAUSTED, scoped VMEM allocation of "
           "32.72M exceeds the 16.00M limit — the (128, 256, R) demand "
           "block pads R to 128 lanes; the kernel is off the solve path")
def test_bucketed_fill_compiles_at_pinned_bucket_shape(one_chip):
    from repro.core.placement import BISECT_STEPS_F32
    from repro.kernels.psdsf_fill_bucketed.kernel import \
        fill_event_levels_bucketed

    # ops.fill_cluster_bucketed_padded pads the bucket axis to 256
    k, b = N_SERVERS, _pad(BUCKET_MAX, 256)
    fill_event_levels_bucketed.lower(
        _shape(one_chip, (k, b)), _shape(one_chip, (k, b)),
        _shape(one_chip, (k, b, N_RES)), _shape(one_chip, (k, N_RES)),
        _shape(one_chip, (k, N_RES)), _shape(one_chip, (k, N_RES)),
        _shape(one_chip, (k,))).compile()


def test_churn_resolve_bucketed_fits_one_chip(one_chip):
    import jax.numpy as jnp

    from repro.core.gamma import gamma_matrix
    from repro.core.instances import sparse_cell_instance
    from repro.core.layout import BucketedLayout
    from repro.sched.churn import ChurnSimulator, _resolve_fn

    prob, _ = sparse_cell_instance()
    assert (prob.num_users, prob.num_servers, prob.num_resources) == (
        N_USERS, N_SERVERS, N_RES)
    assert BucketedLayout.from_support(
        gamma_matrix(prob) > 0).bucket_max == BUCKET_MAX
    max_rounds = inspect.signature(
        ChurnSimulator).parameters["max_rounds"].default
    n, k, r, b = N_USERS, N_SERVERS, N_RES, BUCKET_MAX
    s = lambda shape, dt=None: _shape(one_chip, shape, dt)  # noqa: E731
    compiled = _resolve_fn().lower(
        s((n, r)), s((k, r)), s((n,)), s((n, k)), s((n,), jnp.bool_),
        s((n,), jnp.bool_), s((k,)), s((n, k)), mechanism="psdsf-rdm",
        max_rounds=max_rounds, tol=s(()),
        placement="level", fill="event", round="gauss",
        buckets=(s((k, b), jnp.int32), s((k, b), jnp.bool_)),
        accel="none").compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


def test_churn_telemetry_inputs_feed_the_kernel(one_chip):
    """The churn telemetry's device prep compiles to a program of its own
    whose outputs are the kernel's padded inputs, with no kernel in it."""
    import jax.numpy as jnp

    from repro.sched.churn import _eq16_inputs_fn

    n, k, r = N_USERS, N_SERVERS, N_RES
    s = lambda shape, dt=None: _shape(one_chip, shape, dt)  # noqa: E731
    compiled = _eq16_inputs_fn().lower(
        s((n, r)), s((k, r)), s((n,)), s((n, k)), s((n,), jnp.bool_),
        s((k,)), s((n, k))).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    xphi, gamma = compiled.out_info
    assert xphi.shape == (_pad(n, 256),)
    assert gamma.shape == (_pad(n, 256), k)
    assert xphi.dtype == gamma.dtype == jnp.float32
