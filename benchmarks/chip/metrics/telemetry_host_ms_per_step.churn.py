"""Mean host milliseconds of a window step's telemetry: the
``churn.telemetry`` span less the kernel call inside it (``vds.call``),
so its host gamma matrix, masks and padding."""
from psbench.program_spans import child_ms, mean_per_step


def read(run):
    return mean_per_step(run, lambda s: child_ms(s, "churn.telemetry")
                         - s.total_ms("vds.call"))
