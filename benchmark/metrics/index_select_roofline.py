"""The sparse layers' selection against its roofline: the least time the
chip could take to read each scanned row's float32 score once
(``kernels/sparse_attention.index_select``), over the time of the
operations under the program's ``dsa_select`` scope."""

from kernels import sparse_attention
from metrics import _sparse


def read(view):
    return _sparse.roofline(
        view, _sparse.scope_seconds(view, "dsa_select"),
        lambda scanned, selected, kind: sparse_attention.index_select(
            scanned))
