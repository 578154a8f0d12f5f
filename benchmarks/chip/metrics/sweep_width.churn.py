"""Servers filled per serial step of a device sweep round: the mean
``sweep_width`` counter of the window's ``churn.step`` roots, traced steps
included, read as ``step_compiles.churn`` reads ``compiles``. It is K over
the number of server classes a round fills one after another. ``None``
where no step carries the counter (a program that does not count it)."""
import numpy as np

from psbench.program_spans import steps


def read(run):
    found = steps(run, traced=True)
    if found is None:
        return None
    widths = [s.counters["sweep_width"] for s in found
              if "sweep_width" in s.counters]
    return float(np.mean(widths)) if widths else None
