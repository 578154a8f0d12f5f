"""What a configuration's builder returns: the fleet and the tenants."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Deployment:
    """A fleet of ``K`` servers and ``N`` tenant slots over ``R``
    resources."""
    demands: np.ndarray        # (N, R) per task, as a share of the largest machine
    capacities: np.ndarray     # (K, R)
    weights: np.ndarray        # (N,)
    eligibility: np.ndarray    # (N, K) 0/1

    @property
    def shape(self) -> tuple[int, int, int]:
        """(tenants, servers, resources)."""
        return (self.demands.shape[0], self.capacities.shape[0],
                self.demands.shape[1])


def lognormal(rng: np.random.Generator, median: float, sigma: float,
              lo: float, hi: float, size) -> np.ndarray:
    """Heavy-tailed draws with the given median, clipped to [lo, hi]."""
    return np.clip(median * np.exp(sigma * rng.standard_normal(size)), lo, hi)
