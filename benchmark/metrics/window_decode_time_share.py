"""The window layers' decode kernel's share of the device's busy time: the
own time of the calls named ``paged_decode_window``."""

from metrics import _common, _hybrid


def read(view):
    return _common.share_of_busy(view, _hybrid.named("paged_decode_window"))
