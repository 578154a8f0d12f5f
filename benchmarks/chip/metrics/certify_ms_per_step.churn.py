"""Mean milliseconds of a window step's ``churn.certify`` span: the host
gamma matrix and the active maximum behind the tight-tol certificate."""
from psbench.program_spans import child_ms, mean_per_step


def read(run):
    return mean_per_step(run, lambda s: child_ms(s, "churn.certify"))
