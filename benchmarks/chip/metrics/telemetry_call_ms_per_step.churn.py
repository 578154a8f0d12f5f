"""Mean milliseconds of a window step's ``vds.call`` span: the telemetry
kernel's uploads, its run and the readback, on the host's clock."""
from psbench.program_spans import mean_per_step


def read(run):
    return mean_per_step(run, lambda s: s.total_ms("vds.call"))
