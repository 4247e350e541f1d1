"""The whole hybrid train step's share of the chips' bf16 peak: the
operations the passes require per step (``kernels/hybrid_model_flops``, the
routed pairs as the program counted them) times steps per second of the
traced window, over chips times peak."""

from kernels import hybrid_model_flops
from metrics import _hybrid_train


def read(view):
    obs, traffic = view["observed"], view["traffic"]
    pairs = _hybrid_train.window_pairs(view)
    if view["peaks"] is None or not obs.get("steps") or pairs is None:
        return None
    flops = hybrid_model_flops.train_step_flops(
        obs["sizes"], traffic["batch"], traffic["seq"],
        float(pairs.sum()) / len(pairs))
    rate = obs["steps"] / obs["window_s"]
    return 100.0 * flops * rate / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
