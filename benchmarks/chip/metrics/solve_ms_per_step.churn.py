"""Mean ``ChurnRecord.solve_ms``: the jitted re-solve with its transfers."""
import numpy as np


def read(run):
    recs = [r for r in run.records if "solve_ms" in r]
    return float(np.mean([r["solve_ms"] for r in recs])) if recs else None
