"""The sparse layers' absorbed attention against its roofline: the least
time the chip could take to read the selected latent rows and do their
products with every head's query (``kernels/sparse_attention.
sparse_decode``), over the time of the operations under the program's
``sparse_latent`` scope (the rows' gather and the attention over them)."""

from kernels import sparse_attention
from metrics import _sparse


def read(view):
    return _sparse.roofline(
        view, _sparse.scope_seconds(view, "sparse_latent"),
        lambda scanned, selected, kind: sparse_attention.sparse_decode(
            selected, kind))
