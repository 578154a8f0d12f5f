"""Builds the ``alibaba2018-colo-synth`` deployment from its JSON file and
a seed."""
from __future__ import annotations

import numpy as np

from psbench.deployment import Deployment, lognormal


def build(cfg: dict, rng: np.random.Generator) -> Deployment:
    """Alike machines less what their online containers reserve, and
    batch tenants dealt out evenly over home servers, each also eligible
    on a seeded pool of ``round(pool_share x servers)`` other servers."""
    k, n = cfg["servers"], cfg["tenants"]
    pool = round(cfg["pool_share"] * k)
    if not 0 < pool < k:
        raise ValueError(f"a pool of {pool} of {k} servers is not a pool")
    caps = np.clip(np.asarray(cfg["machine"], float) - online(cfg, rng, k),
                   0.0, None)

    cpu = lognormal(rng, **_args(cfg["cpu_request"]), size=n)
    mem = lognormal(rng, **_args(cfg["memory_request"]), size=n)
    demands = np.stack([cpu, mem], axis=1)
    bands = cfg["priority_bands"]
    weights = rng.choice(np.asarray(bands["weights"], float), size=n,
                         p=np.asarray(bands["shares"], float))

    _, elig = eligibility(rng, n, k, pool)
    return Deployment(demands, caps, weights, elig)


def eligibility(rng: np.random.Generator, n: int, k: int,
                pool: int) -> tuple[np.ndarray, np.ndarray]:
    """(home server of each tenant, (N, K) 0/1 eligibility): the homes
    dealt out evenly over a seeded order, and each tenant's pool of
    ``pool`` other servers drawn without repeats."""
    home = rng.permutation(n) % k
    keys = rng.random((n, k))
    keys[np.arange(n), home] = 2.0            # never drawn into the pool
    others = np.argpartition(keys, pool - 1, axis=1)[:, :pool]
    elig = np.zeros((n, k))
    elig[np.arange(n), home] = 1.0
    elig[np.arange(n)[:, None], others] = 1.0
    return home, elig


def online(cfg: dict, rng: np.random.Generator, k: int) -> np.ndarray:
    """(K, 2) CPU and memory that the online containers on each machine
    reserve, as shares of the machine."""
    spec = cfg["online_containers"]
    per = rng.poisson(spec["mean"], size=k)
    total = int(per.sum())
    cores = rng.choice(np.asarray(spec["cpu_cores"], float), size=total)
    mem = lognormal(rng, **_args(spec["memory"]), size=total)
    machine = np.repeat(np.arange(k), per)
    return np.stack([np.bincount(machine, cores, k) / cfg["machine_cores"],
                     np.bincount(machine, mem, k)], axis=1)


def _args(spec: dict) -> dict:
    return dict(median=spec["median"], sigma=spec["sigma"], lo=spec["min"],
                hi=spec["max"])
