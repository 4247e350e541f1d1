"""The flash attention kernels' share of the device's busy time."""

from metrics import _common


def read(view):
    return _common.share_of_busy(view, _common.is_flash_kernel)
