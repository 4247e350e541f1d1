"""Share of the traced window in which no operation ran on the device."""

from metrics import _common


def read(view):
    return _common.idle_share(view)
