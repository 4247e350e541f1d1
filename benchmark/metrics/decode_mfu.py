"""The whole decode step's share of the chip's bf16 peak: 2 N FLOP per
delivered token times tokens per second of the traced window, over peak."""

from kernels import model_flops


def read(view):
    obs = view["observed"]
    if view["peaks"] is None or not obs.get("tokens"):
        return None
    flops = model_flops.decode_token_flops(view["reference"],
                                           obs["sizes"])
    return 100.0 * flops * obs["tokens"] / obs["window_s"] / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
