"""Operations and bytes of learned sparse attention over a latent cache
(DeepSeek-V3.2's DSA as GLM-5 serves it), from counts of the model's own
rows, whatever implements it (``apex_tpu/serving/sparse_attention.py``
today).

- The index scan: each scanned row (a cached token a query's indexer
  scores) is one index key of ``index_dim`` elements read, and ``2 *
  index_heads * index_dim`` FLOP of products (the ReLU and the heads'
  weighted sum are elementwise, and not counted).
- The select: one read of each scanned row's float32 score; no FLOP.
- The sparse decode: the absorbed latent attention of
  ``latent_attention.decode_rows`` over the selected rows only.

Rows as the model defines them: not padded pages, lanes or columns past a
slot's length, which an implementation may read but the model does not
need.
"""

from kernels import latent_attention


def index_scan(rows, kind, itemsize=2):
    """FLOP and bytes of scoring ``rows`` cached tokens (all layers)."""
    flops = rows * 2 * kind["index_heads"] * kind["index_dim"]
    return flops, rows * kind["index_dim"] * itemsize


def index_select(rows):
    """FLOP and bytes of selecting among ``rows`` scored tokens."""
    return 0, 4 * rows


def sparse_decode(rows, kind, itemsize=2):
    """FLOP and bytes of attending ``rows`` selected rows (all layers)."""
    return latent_attention.decode_rows(rows, kind, 1, itemsize)
