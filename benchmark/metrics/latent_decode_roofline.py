"""The latent layers' decode kernel against its roofline: the least time
the chip could take for the cached rows every decoding slot's heads score
against, in each latent layer (the greater of their products over the peak
and their bytes over the bandwidth), over the time of
``paged_decode_latent``."""

from kernels import latent_attention
from lib import peaks, xplane
from metrics import _hybrid


def read(view):
    seconds, count = xplane.op_seconds(view["trace"],
                                       _hybrid.named("paged_decode_latent"))
    ticks = _hybrid.phase_fields(view, "decode_plan", "kv_tokens_latent")
    if not count or not ticks or view["peaks"] is None:
        return None
    sz = view["observed"]["sizes"]
    flops, nbytes = latent_attention.decode_rows(
        sum(t["kv_tokens_latent"] for t in ticks), sz["kinds"][0],
        len(sz["pattern"]))
    return 100.0 * peaks.roofline_seconds(
        flops, nbytes, view["peaks"], view["chips"]) / seconds
