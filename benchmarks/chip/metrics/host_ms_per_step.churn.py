"""Mean host milliseconds of a churn step outside the solve: the harness
clock around ``ChurnSimulator.step`` minus the step's ``solve_ms``."""
import numpy as np


def read(run):
    recs = [r for r in run.records if "solve_ms" in r]
    if not recs:
        return None
    return float(np.mean([1e3 * r["step_s"] - r["solve_ms"] for r in recs]))
