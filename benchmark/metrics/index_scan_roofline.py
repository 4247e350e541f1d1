"""The sparse layers' index scan against its roofline: the least time the
chip could take to read the index key of every row the window's decode
calls scanned and form its products with the index heads' queries
(``kernels/sparse_attention.index_scan``), over the time of
``paged_index_decode``."""

from kernels import sparse_attention
from metrics import _sparse


def read(view):
    return _sparse.roofline(
        view, _sparse.index_kernel_seconds(view),
        lambda scanned, selected, kind: sparse_attention.index_scan(
            scanned, kind))
