"""The full-attention layers' decode kernel against its roofline: the whole
history of every decoding slot, in each full layer, over the time of
``paged_decode_full``."""

from metrics import _hybrid


def read(view):
    return _hybrid.decode_roofline(view, "paged_decode_full",
                                   "kv_tokens_full", 0)
