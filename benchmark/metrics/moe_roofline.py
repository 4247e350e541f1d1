"""The expert layers' grouped matmuls against their roofline: the least time
the chip could take to read the weights of the experts each call hit, once a
call, and do the routed pairs' products, over the time the kernels took."""

from kernels import moe
from lib import peaks, xplane
from metrics import _hybrid


def read(view):
    seconds, count = xplane.op_seconds(view["trace"],
                                       _hybrid.named("moe_experts"))
    routed = _hybrid.routed_calls(view)
    if not count or routed is None or view["peaks"] is None:
        return None
    sz = view["observed"]["sizes"]
    flops, nbytes = moe.routed(*routed, sz["hidden"], sz["expert_ffn"])
    return 100.0 * peaks.roofline_seconds(
        flops, nbytes, view["peaks"], view["chips"]) / seconds
