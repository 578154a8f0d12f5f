"""The phases of ``chip_smoke.py`` at a tiny size on the CPU backend.

The script's own entry point refuses to run without a TPU; here its phase
functions run directly, with the Pallas telemetry kernel interpreted
because the backend is the CPU. Importing the script touches no device.
"""
import importlib.util
from pathlib import Path

import pytest

from repro.core.instances import sparse_cell_instance

# same density law as the script's instances, cut to a size that still
# gives every user more than one eligible server (m = 2 of 8 per cell pair)
TINY_USERS, TINY_SERVERS = 600, 64


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_phase_parity_tiny(chip_smoke):
    lines = []
    out = chip_smoke.phase_parity(TINY_USERS, TINY_SERVERS,
                                  log=lines.append)
    assert out["trajectory_rel_diff"] <= chip_smoke.TRAJ_RTOL
    assert out["vds_rel_diff"] <= chip_smoke.VDS_RTOL
    assert any(line.startswith("phase1 cold jax: layout=bucketed")
               for line in lines)


def test_phase_churn_tiny(chip_smoke):
    prob, _ = sparse_cell_instance(num_users=TINY_USERS,
                                   num_servers=TINY_SERVERS)
    lines = []
    out = chip_smoke.phase_churn(prob, steps=10, log=lines.append)
    assert out["steps"] >= 10
    assert len(out["records"]) == out["steps"] + 1
    step_lines = [ln for ln in lines if ln.startswith("phase2 step=")]
    assert len(step_lines) == out["steps"] + 1
    assert all("layout=bucketed" in ln for ln in step_lines)
    kinds = " ".join(step_lines)
    assert all(k in kinds for k in ("arrival", "departure", "degrade"))


def test_phase_churn_rejects_dense_layout(chip_smoke):
    # a dense instance resolves layout="auto" to dense: the phase must
    # fail loudly instead of smoke-testing the wrong path
    prob, _ = sparse_cell_instance(num_users=40, num_servers=16,
                                   density=0.5, cells=4)
    with pytest.raises(chip_smoke.SmokeFailure, match="resolved to 'dense'"):
        chip_smoke.phase_churn(prob, steps=10, log=lambda line: None)
