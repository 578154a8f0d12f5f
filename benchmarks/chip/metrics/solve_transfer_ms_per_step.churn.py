"""Mean milliseconds of a window step's solve transfers: the
``churn.solve.upload`` span (activity, degrades and the float32 warm
start to the device) plus ``churn.solve.download`` (the allocation back,
as float64, and the scalars)."""
from psbench.program_spans import mean_per_step


def read(run):
    return mean_per_step(run, lambda s: s.total_ms("churn.solve.upload")
                         + s.total_ms("churn.solve.download"))
