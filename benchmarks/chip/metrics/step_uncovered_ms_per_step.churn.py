"""Mean milliseconds of a window step that none of the ``churn.step``
span's children covers (record assembly, the fill budget, and any host
work a later change adds outside every span)."""
from psbench.program_spans import mean_per_step


def read(run):
    return mean_per_step(run, lambda s: s.self_ms())
