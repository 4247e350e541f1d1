"""The sliding-window layers' flash attention kernels, forward and backward,
against their roofline (K and V counted at their own heads, a query's keys
at ``min(i + 1, window)``)."""

from metrics import _hybrid_train


def read(view):
    return _hybrid_train.flash_roofline(view, "flash_window", True)
