"""Microseconds of ``churn.solve.wait`` per serial class fill, over the
untraced window steps: their summed wait over their summed
``serial_fills`` counters. The wait holds the resolve's dispatch and the
fixed device work around its rounds too, spread here over the fills, so
this is an upper bound on the device time of one class fill. ``None``
where no step carries the counter (a program that does not count it)."""
from psbench.program_spans import steps


def read(run):
    found = steps(run)
    if not found:
        return None
    counted = [s for s in found if "serial_fills" in s.counters]
    fills = sum(s.counters["serial_fills"] for s in counted)
    if fills <= 0:
        return None
    return 1e3 * sum(s.total_ms("churn.solve.wait") for s in counted) / fills
