"""Mean milliseconds of a window step's ``churn.solve.wait`` span: the
jitted re-solve's dispatch and the host's wait for its result."""
from psbench.program_spans import mean_per_step


def read(run):
    return mean_per_step(run, lambda s: s.total_ms("churn.solve.wait"))
