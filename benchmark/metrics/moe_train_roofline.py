"""The expert layers' grouped matmuls, forward and backward, against their
roofline: the least time the chip could take for the routed pairs' products
and the hit experts' matrices (read once forward, once backward, their
gradient written once) over the time the kernels took."""

from kernels import moe_train
from lib import peaks, xplane
from metrics import _hybrid, _hybrid_train


def read(view):
    seconds, count = xplane.op_seconds(view["trace"],
                                       _hybrid.named("moe_experts"))
    pairs = _hybrid_train.window_pairs(view)
    if not count or pairs is None or view["peaks"] is None:
        return None
    sz = view["observed"]["sizes"]
    flops, nbytes = moe_train.routed(
        int(pairs.sum()), int((pairs > 0).sum()), sz["hidden"],
        sz["expert_ffn"])
    return 100.0 * peaks.roofline_seconds(
        flops, nbytes, view["peaks"], view["chips"]) / seconds
