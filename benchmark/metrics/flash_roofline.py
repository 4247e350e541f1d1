"""Flash attention, forward and backward, against its roofline: the least
time the chips could take for attention's operations and bytes of the
traced steps, over the time the kernels took.  Each chip's kernels run its
share of the heads and layers, so the whole step's work is held against
all the chips and the busiest chip's kernel time."""

from kernels import flash_attention
from lib import peaks, xplane
from metrics import _common


def read(view):
    obs, traffic = view["observed"], view["traffic"]
    seconds, count = xplane.op_seconds(view["trace"], _common.is_flash_kernel)
    if not count or view["peaks"] is None:
        return None
    flops, nbytes = flash_attention.train_step(
        obs["sizes"], traffic["batch"], traffic["seq"])
    least = obs["steps"] * peaks.roofline_seconds(
        flops, nbytes, view["peaks"], view["chips"])
    return 100.0 * least / seconds
