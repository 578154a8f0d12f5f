"""Mean serial class fills per untraced window step: the ``serial_fills``
counter of the step's ``churn.step`` root, its sweep rounds times the
colour classes a round fills one after another. ``None`` where no step
carries the counter (a program that does not count it)."""
import numpy as np

from psbench.program_spans import steps


def read(run):
    found = steps(run)
    if not found:
        return None
    fills = [s.counters["serial_fills"] for s in found
             if "serial_fills" in s.counters]
    return float(np.mean(fills)) if fills else None
