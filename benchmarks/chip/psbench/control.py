"""The numbers the correctness check compares (``sound``), and the control
that has to fail them (``answer``): the plain reference put in the
program's place and computed in bfloat16, one step below the float32 that
the configurations state, and the faults a churn step can have.

``correct`` must come out false for the control and for each fault when
they go through ``harness.check`` with the cell's limits.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from psbench import reference

NUMBERS = ("capacity_excess", "saturation_gap", "vds_rel_err")

#: Gauss-Seidel passes the control makes at most from an empty allocation
CONTROL_PASSES = 16


def _numbers(x, sample, g, names, vds_reported) -> dict:
    out = {}
    if "capacity_excess" in names:
        out["capacity_excess"] = reference.capacity_excess(
            x, sample.demands, sample.capacities)
    if "saturation_gap" in names:
        out["saturation_gap"] = reference.saturation_gap(
            x, sample.demands, sample.capacities, g)
    if "vds_rel_err" in names and vds_reported is not None:
        out["vds_rel_err"] = reference.vds_rel_err(vds_reported, x,
                                                   sample.weights, g)
    return out


def sound(sample, names=NUMBERS) -> dict:
    """The numbers for the answer ``sample`` holds."""
    x = np.asarray(sample.x, dtype=np.float64)
    g = reference.gamma(sample.demands, sample.capacities,
                        sample.eligibility)
    return _numbers(x, sample, g, names, sample.min_vds)


def answer(sample, tol: float, passes: int = CONTROL_PASSES,
           rounding=reference.bfloat16):
    """``sample`` with the control's answer in place of the program's: the
    reference solves the same state in bfloat16 (or ``rounding``) by
    Gauss-Seidel passes from an empty allocation, until a pass changes no
    entry by more than ``tol`` of the gamma scale or after ``passes``, and
    reports Eq. 16 in the same precision. Nothing of the program's answer
    goes in."""
    g = reference.gamma(sample.demands, sample.capacities,
                        sample.eligibility)
    x = np.zeros(g.shape)
    for _ in range(passes):
        nxt = reference.sweep(x, sample.demands, sample.capacities,
                              sample.weights, g, rounding=rounding)
        moved = np.abs(nxt - x).max()
        x = nxt
        if moved <= tol * reference.scale_of(g):
            break
    vds = (None if sample.min_vds is None else reference.min_vds(
        x, sample.weights, g, rounding=rounding))
    return dataclasses.replace(sample, x=x, min_vds=vds)


def unchanged(sample, x_before):
    """``sample`` as steps that returned their state unchanged would leave
    it: the allocation ``x_before`` with the departed tenants' rows
    cleared (the simulator clears them as it applies the events), against
    the sample's state, with Eq. 16 read from that allocation."""
    present = (np.asarray(sample.eligibility) > 0).any(axis=1)
    x = np.array(x_before, np.float64) * present[:, None]
    g = reference.gamma(sample.demands, sample.capacities,
                        sample.eligibility)
    vds = (None if sample.min_vds is None
           else reference.min_vds(x, sample.weights, g))
    return dataclasses.replace(sample, x=x, min_vds=vds)


def altered(sample, server: int = 0, factor: float = 1.05):
    """``sample`` with one server's column of the answer scaled."""
    x = np.array(sample.x, dtype=np.float64)
    x[:, server] *= factor
    return dataclasses.replace(sample, x=x)
