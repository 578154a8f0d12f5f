"""Sharding-rule lowering tests on a small forced-device mesh (subprocess so
the 8-device XLA flag doesn't leak into other tests)."""
import json
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config, SHAPES
    from repro.launch.sharding import (ShardingOptions, batch_specs,
                                       cache_specs, named, opt_state_specs,
                                       param_specs, sanitize_specs)
    from repro.train.optimizer import OptimizerConfig
    from repro.train.step import abstract_train_state, build_train_step
    from repro.launch.specs import batch_sds, decode_sds
    from repro.train.step import build_decode_step
    from repro.models import abstract_params

    # Auto axes: jax 0.9 makes mesh axes Explicit by default, and the
    # model code places activations with with_sharding_constraint
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         devices=jax.devices()[:8],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    results = {}
    for arch in ("qwen3_1_7b", "jamba_v0_1_52b", "granite_moe_3b_a800m"):
        cfg = get_smoke_config(arch)
        # widen dims so they shard over the tiny mesh
        import dataclasses
        cfg = dataclasses.replace(cfg, dp_axes=("data",), tp_axis="model")
        oc = OptimizerConfig()
        opts = ShardingOptions()
        with mesh:
            step = build_train_step(cfg, oc)
            state_abs = abstract_train_state(cfg, oc)
            batch_abs = batch_sds(cfg, 8, 32, "train")
            pspec = param_specs(cfg, mesh, opts)
            sspec = sanitize_specs({"params": pspec,
                                    "opt": opt_state_specs(pspec)},
                                   state_abs, mesh)
            bspec = sanitize_specs(batch_specs(cfg, mesh, "train", opts),
                                   batch_abs, mesh)
            comp = jax.jit(step,
                           in_shardings=(named(mesh, sspec),
                                         named(mesh, bspec)),
                           out_shardings=(named(mesh, sspec),
                                          NamedSharding(mesh, P())),
                           donate_argnums=(0,)
                           ).lower(state_abs, batch_abs).compile()
            results[arch] = int(comp.memory_analysis().temp_size_in_bytes)
            # decode path too
            dstep = build_decode_step(cfg)
            params_abs = abstract_params(cfg)
            caches, token, pos = decode_sds(cfg, 16, 64)
            cspec = sanitize_specs(cache_specs(cfg, mesh, 16, opts),
                                   caches, mesh)
            pspec2 = sanitize_specs(pspec, params_abs, mesh)
            jax.jit(dstep,
                    in_shardings=(named(mesh, pspec2), named(mesh, cspec),
                                  NamedSharding(mesh, P(("data",))),
                                  NamedSharding(mesh, P())),
                    donate_argnums=(1,)
                    ).lower(params_abs, caches, token, pos).compile()
    print("RESULT:" + json.dumps(results))
""")


def test_small_mesh_lowering_compiles():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=900, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, out.stdout[-2000:]
    results = json.loads(line[0][len("RESULT:"):])
    assert set(results) == {"qwen3_1_7b", "jamba_v0_1_52b",
                            "granite_moe_3b_a800m"}
    for arch, temp in results.items():
        assert temp > 0, arch
