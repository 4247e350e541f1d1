"""Operations and bytes of decode attention over the cache groups of a
model whose layers are of more than one kind, from counts, whatever
implements it (``apex_tpu/serving/paged_attention`` today).

A decode tick reads, in each layer of a group, the rows of keys and values
its slots' queries can see: the whole history in a full group, what lies
inside the window in a window group.  A row is ``kv_heads * (k_dim +
v_dim)`` elements; ``q k^T`` and ``p v`` over it are ``2 * heads * (k_dim +
v_dim)`` FLOP.  Rows, not padded pages: what any implementation must read.
"""


def decode_rows(rows, kind, layers, itemsize=2):
    """FLOP and bytes of ``layers`` layers of attention kind ``kind`` (the
    reference's: heads, kv_heads, k_dim, v_dim) attending ``rows`` cache
    rows in all."""
    width = kind["k_dim"] + kind["v_dim"]
    flops = layers * rows * 2 * kind["heads"] * width
    nbytes = layers * rows * kind["kv_heads"] * width * itemsize
    return flops, nbytes
