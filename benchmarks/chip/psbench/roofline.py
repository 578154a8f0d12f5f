"""Operations and bytes of the program's kernels, from their shapes."""
from __future__ import annotations

#: the ``psdsf_vds`` kernel's tiles, as its padding wrapper sets them
VDS_BLOCK_N, VDS_BLOCK_K = 256, 128


def vds_padded_shape(num_users: int, num_servers: int) -> tuple[int, int]:
    """(N, K) as the ``psdsf_vds`` wrapper pads them to whole tiles."""
    bn, bk = min(VDS_BLOCK_N, num_users), min(VDS_BLOCK_K, num_servers)
    return num_users + (-num_users % bn), num_servers + (-num_servers % bk)


def vds_bytes(num_users: int, num_servers: int) -> int:
    """Least HBM bytes one ``psdsf_vds`` call moves at the padded shape:
    the (N, K) float32 gamma and the (N,) float32 totals read once, and the
    (K,) float32 minima and int32 arg-minima written. The kernel does one
    division and two comparisons per gamma entry, far below what a chip
    computes in the time its bytes take, so bandwidth bounds it."""
    n, k = vds_padded_shape(num_users, num_servers)
    return 4 * (n * k + n + 2 * k)
