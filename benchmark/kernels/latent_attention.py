"""Operations and bytes of decode attention over a latent cache (multi-head
latent attention, arXiv:2405.04434), from counts, whatever implements it
(``apex_tpu/serving/paged_attention.paged_decode_latent`` today).

A decode tick reads, in each latent layer, one cached row a token of its
slots' histories: the normed latent beside the one rotated key all heads
share, ``kv_rank + rope`` elements.  Every head scores against the whole
row and takes its values from the row's latent channels: ``2 * heads *
((kv_rank + rope) + kv_rank)`` FLOP a row.  Rows as the model defines them,
not padded pages or lanes: what any implementation must read.
"""


def row_elements(kind):
    return kind["kv_rank"] + kind["rope"]


def decode_rows(rows, kind, layers, itemsize=2):
    """FLOP and bytes of ``layers`` latent layers of attention kind ``kind``
    (the reference's: heads, kv_rank, rope) attending ``rows`` cache rows
    in all."""
    flops = layers * rows * 2 * kind["heads"] * (
        row_elements(kind) + kind["kv_rank"])
    nbytes = layers * rows * row_elements(kind) * itemsize
    return flops, nbytes


def expanded_bytes_per_token(kind, layers, itemsize=2):
    """What a token would hold were every head's keys and values cached."""
    return layers * kind["heads"] * (kind["k_dim"] + kind["v_dim"]) * itemsize
