"""Builds the ``gcd2011-synth`` deployment from its JSON file and a seed."""
from __future__ import annotations

import numpy as np

from psbench.deployment import Deployment, lognormal


def build(cfg: dict, rng: np.random.Generator) -> Deployment:
    """Servers in the trace's machine classes, shuffled over ``rack_groups``
    groups of consecutive servers. Each group is home to the same number
    of tenants; a ``home_only_share`` of them use ``servers_per_tenant``
    servers of their home group, the rest half of them there and half in
    the next group. Inside a group the tenants' servers are dealt out in
    turn over a seeded order of its servers, so every server is eligible
    to the same number of tenants."""
    k, n, m = cfg["servers"], cfg["tenants"], cfg["servers_per_tenant"]
    groups = cfg["rack_groups"]
    per = k // groups
    if k % groups or n % groups or m % 2 or m > per or (m * n) % k:
        raise ValueError(f"{n} tenants on {m} of {k} servers in {groups} "
                         f"groups cannot be placed evenly")
    counts = np.asarray(cfg["class_counts_per_120"], float)
    share = counts / counts.sum() * k
    per_class = np.floor(share).astype(int)
    per_class[np.argsort(per_class - share)[:k - per_class.sum()]] += 1
    cls = rng.permutation(np.repeat(np.arange(len(counts)), per_class))
    caps = np.asarray(cfg["machine_classes"], float)[cls]

    cpu = lognormal(rng, **_args(cfg["cpu_request"]), size=n)
    mem = np.clip(cpu * lognormal(rng, cfg["memory_per_cpu"]["median"],
                                  cfg["memory_per_cpu"]["sigma"], 0.0,
                                  np.inf, n),
                  cfg["memory_per_cpu"]["min"], cfg["memory_per_cpu"]["max"])
    demands = np.stack([cpu, mem], axis=1)
    bands = cfg["priority_bands"]
    weights = rng.choice(np.asarray(bands["weights"], float), size=n,
                         p=np.asarray(bands["shares"], float))

    per_group = n // groups
    slot = rng.permutation(n)                # tenant -> slot in its group
    home = slot % groups
    home_only = slot // groups < round(cfg["home_only_share"] * per_group)
    elig = np.zeros((n, k))
    for g in range(groups):
        here = np.flatnonzero(home == g)
        spill = np.flatnonzero((home == (g - 1) % groups) & ~home_only)
        users = np.concatenate([here, spill])
        want = np.concatenate([np.where(home_only[here], m, m // 2),
                               np.full(spill.size, m // 2)])
        order = rng.permutation(users.size)
        users, want = users[order], want[order]
        start = np.cumsum(want) - want
        servers = g * per + rng.permutation(per)
        rows = np.repeat(users, want)
        turn = np.repeat(start, want) + _ranks(want)
        elig[rows, servers[turn % per]] = 1.0
    return Deployment(demands, caps, weights, elig)


def _ranks(counts: np.ndarray) -> np.ndarray:
    """0, 1, ..., c - 1 for each count c, concatenated."""
    ends = np.cumsum(counts)
    return np.arange(ends[-1]) - np.repeat(ends - counts, counts)


def _args(spec: dict) -> dict:
    return dict(median=spec["median"], sigma=spec["sigma"], lo=spec["min"],
                hi=spec["max"])
