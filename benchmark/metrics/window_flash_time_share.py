"""The sliding-window layers' flash attention kernels' share of the device's
busy time: the own time of the kernels the trainer names ``flash_window``."""

from metrics import _common, _hybrid


def read(view):
    return _common.share_of_busy(view, _hybrid.named("flash_window"))
