"""The whole train step's share of the chips' bf16 peak: the operations the
passes require per step (``kernels/model_flops``) times steps per second of
the traced window, over chips times peak."""

from kernels import model_flops


def read(view):
    obs, traffic = view["observed"], view["traffic"]
    if view["peaks"] is None or not obs.get("steps"):
        return None
    flops = model_flops.train_step_flops(
        view["reference"], obs["sizes"], traffic["batch"],
        traffic["seq"])
    rate = obs["steps"] / obs["window_s"]
    return 100.0 * flops * rate / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
