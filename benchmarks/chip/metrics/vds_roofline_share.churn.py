"""Share of its roofline that the ``psdsf_vds`` kernel reaches in the
traced window: the least time its bytes take at the chip's HBM bandwidth
(``psbench.roofline.vds_bytes``, one call per traced step) over its
measured device time. Bandwidth bounds this kernel."""
from psbench.roofline import vds_bytes

PATTERNS = ("vds_argmin", "_vds_kernel")


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs, count = run.trace.kernel(PATTERNS)
    steps = run.trace.spans.get("step", 0)
    if count == 0 or steps == 0 or secs <= 0:
        return None
    n, k, _ = run.shape
    least = steps * vds_bytes(n, k) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
