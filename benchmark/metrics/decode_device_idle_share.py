"""Share of the traced window in which no operation ran on the device:
the host's work between and inside ticks."""

from metrics import _common


def read(view):
    return _common.idle_share(view)
