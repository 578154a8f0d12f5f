"""Mean milliseconds of a window step's ``churn.apply`` span: applying the
step's events, and rebuilding the bucket layout when an arrival needs it
(``churn.rebuild`` lies inside)."""
from psbench.program_spans import child_ms, mean_per_step


def read(run):
    return mean_per_step(run, lambda s: child_ms(s, "churn.apply"))
