"""Jitted wrappers used by the cluster scheduler's jitted tick and the
scheduler-telemetry callers (DistributedPSDSF.min_vds, ChurnSimulator)."""
from __future__ import annotations

import numpy as np

from repro.core.trace import count, span

from .kernel import vds_argmin  # noqa: F401 (public op == kernel entry)


def min_vds_padded(x_over_phi, gamma, *, interpret: bool = False):
    """(min normalized VDS, argmin user) per server for arbitrary (N, K).

    Pads both axes to the kernel's block multiples (padded users carry
    gamma == 0 -> +inf, padded server columns are sliced off), so callers
    don't have to know the tiling. Inputs are host arrays or jnp arrays;
    returns numpy (min (K,), argmin (K,) int32).
    """
    import jax.numpy as jnp

    with span("vds.prep"):
        x_over_phi = np.asarray(x_over_phi)
        gamma = np.asarray(gamma)
        n, k = gamma.shape
        block_n, block_k = min(256, max(n, 1)), min(128, max(k, 1))
        n_pad, k_pad = -n % block_n, -k % block_k
        if n_pad or k_pad:
            x_over_phi = np.pad(x_over_phi, (0, n_pad))
            gamma = np.pad(gamma, ((0, n_pad), (0, k_pad)))
    with span("vds.call"):
        up = (jnp.asarray(x_over_phi, jnp.float32),
              jnp.asarray(gamma, jnp.float32))
        count("h2d_bytes", sum(a.nbytes for a in up))
        mn, arg = vds_argmin(*up, block_n=block_n, block_k=block_k,
                             interpret=interpret)
        mn, arg = np.asarray(mn), np.asarray(arg)
        count("d2h_bytes", mn.nbytes + arg.nbytes)
    return mn[:k], arg[:k]
