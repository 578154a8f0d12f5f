"""Median, over every event due in the window, of the time from its due
time to the return of the step that certified quotas for it."""
import numpy as np


def read(run):
    if run.latencies_s is None or run.latencies_s.size == 0:
        return None
    return 1e3 * float(np.percentile(run.latencies_s, 50))
