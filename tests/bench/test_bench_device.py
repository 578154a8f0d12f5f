"""The chip benchmark refuses any backend but a TPU, and a chip whose
peaks it does not know; the vds kernel's bytes come from its padded
shapes."""
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from psbench import device, roofline  # noqa: E402


def test_cpu_backend_is_refused():
    import jax

    with pytest.raises(device.DeviceError, match="needs a TPU"):
        device.require_tpu(1, jax.devices())


def test_too_few_chips_and_unknown_kind_are_refused():
    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    with pytest.raises(device.DeviceError, match="needs 4 chips"):
        device.require_tpu(4, [tpu])
    assert device.require_tpu(1, [tpu, tpu]) == [tpu]
    odd = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(device.DeviceError, match="no peaks"):
        device.require_tpu(1, [odd])


def test_run_cell_exits_nonzero_and_prints_no_result_on_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
         "gcd2011-synth-churn", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("n, k, padded", [
    (20000, 256, (20224, 256)),
    (2048, 512, (2048, 512)),
    (600, 32, (768, 32)),
])
def test_vds_bytes_at_padded_shapes(n, k, padded):
    assert roofline.vds_padded_shape(n, k) == padded
    pn, pk = padded
    assert roofline.vds_bytes(n, k) == 4 * (pn * pk + pn + 2 * pk)
