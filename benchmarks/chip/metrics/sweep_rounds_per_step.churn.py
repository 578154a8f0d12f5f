"""Mean sweep rounds per churn step (``ChurnRecord.rounds``)."""
import numpy as np


def read(run):
    recs = [r for r in run.records if "rounds" in r]
    return float(np.mean([r["rounds"] for r in recs])) if recs else None
