"""Percent of the traced window in which no operation ran on the device,
in a churn cell."""


def read(run):
    if run.trace is None or "solve_ms" not in (run.traced_records or [{}])[0]:
        return None
    return 100.0 * run.trace.idle_share
