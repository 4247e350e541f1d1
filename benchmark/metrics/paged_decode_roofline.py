"""The paged decode kernel against its roofline: the least time the chip
could take to read the live keys and values of every decode tick of the
traced window (and do its products), over the time the kernel took."""

from kernels import paged_attention
from lib import peaks, xplane
from metrics import _common


def read(view):
    obs = view["observed"]
    seconds, count = xplane.op_seconds(
        view["trace"], _common.paged_decode_kernel(view))
    if not count or view["peaks"] is None:
        return None
    sz = obs["sizes"]
    d = sz["hidden"] // sz["heads"]
    least = 0.0
    for tick in obs["ticks_seen"]:
        if tick.kv_tokens:
            flops, nbytes = paged_attention.decode(
                tick.kv_tokens, sz["heads"], sz["heads"], d, sz["layers"])
            least += peaks.roofline_seconds(
                flops, nbytes, view["peaks"], view["chips"])
    return 100.0 * least / seconds
