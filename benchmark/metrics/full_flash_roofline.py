"""The full-attention layers' flash attention kernels, forward and backward,
against their roofline (K and V counted at their own heads)."""

from metrics import _hybrid_train


def read(view):
    return _hybrid_train.flash_roofline(view, "flash_full", False)
