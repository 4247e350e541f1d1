"""The window layers' decode kernel against its roofline: the rows inside
the window of every decoding slot, in each window layer, over the time of
``paged_decode_window``."""

from metrics import _hybrid


def read(view):
    return _hybrid.decode_roofline(view, "paged_decode_window",
                                   "kv_tokens_window", 1)
