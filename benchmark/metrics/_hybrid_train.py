"""What the readers of the hybrid trainer's per-layer metrics share: the
pairs the routers counted in the traced window (``TrainStats.moe_pairs`` of
every counted step, which the driver keeps) and the flash kernels by the
names the trainer gives them.  A run without those counts or names gives
nothing to read."""

from kernels import hybrid_attention
from lib import peaks, xplane
from metrics import _hybrid


def window_pairs(view):
    """``[steps, microbatches, expert layers, held]`` pairs of the window's
    steps, or ``None`` where the run kept none."""
    pairs = view["observed"].get("moe_pairs")
    return pairs if pairs is not None and len(pairs) else None


def flash_roofline(view, kernel, sliding):
    """Least time for the attention of every layer of one kind, forward and
    backward, in the traced steps over the time of the kernels named
    ``kernel``."""
    obs, traffic = view["observed"], view["traffic"]
    seconds, count = xplane.op_seconds(view["trace"], _hybrid.named(kernel))
    if not count or view["peaks"] is None or not obs.get("steps"):
        return None
    flops, nbytes = hybrid_attention.train_step(
        obs["sizes"], sliding, traffic["batch"], traffic["seq"])
    return 100.0 * obs["steps"] * peaks.roofline_seconds(
        flops, nbytes, view["peaks"], view["chips"]) / seconds
