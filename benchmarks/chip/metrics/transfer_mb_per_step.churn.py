"""Mean megabytes (1e6 bytes) a window step moves between host and
device: its ``h2d_bytes`` and ``d2h_bytes`` counters (the solve's uploads
and download, the telemetry kernel's, and a layout rebuild's)."""
from psbench.program_spans import mean_per_step


def read(run):
    return mean_per_step(run, lambda s: 1e-6 * (
        s.counters.get("h2d_bytes", 0) + s.counters.get("d2h_bytes", 0)))
