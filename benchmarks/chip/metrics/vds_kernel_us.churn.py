"""Device microseconds of the ``psdsf_vds`` telemetry kernel per churn
step in the traced window: the summed device time of its events over the
steps traced."""
PATTERNS = ("vds_argmin", "_vds_kernel")


def read(run):
    if run.trace is None:
        return None
    secs, count = run.trace.kernel(PATTERNS)
    steps = run.trace.spans.get("step", 0)
    if count == 0 or steps == 0:
        return None
    return 1e6 * secs / steps
