"""Operations and bytes of attention over a paged KV cache, from shapes,
whatever implements it (``apex_tpu/serving/paged_attention`` today).

Decoding one token in a slot whose history is ``t`` tokens reads the keys
and values of those ``t`` tokens once (``2 * t * kv_heads * d`` elements)
and does ``q k^T`` and ``p v``: ``2 * 2 * heads * d * t`` FLOP.  Queries
and outputs are counted too; they are small beside the cache.
"""


def decode(history_tokens, heads, kv_heads, d, layers, cache_itemsize=4,
           act_itemsize=2):
    """FLOP and bytes of one decode tick over slots whose histories are
    ``history_tokens`` (a list), through every layer."""
    t = sum(history_tokens)
    b = len(history_tokens)
    flops = layers * 4 * heads * d * t
    nbytes = layers * (2 * t * kv_heads * d * cache_itemsize
                       + 2 * b * heads * d * act_itemsize)
    return flops, nbytes

