"""The chip's published peaks, keyed by the exact ``device_kind``."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "peaks.json")


def of(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device_kind {device_kind!r}: "
                       f"add it to {_PATH} with its source")
    return table[device_kind]


def roofline_seconds(flops, nbytes, peaks: dict, chips: int = 1) -> float:
    """The least time the chips could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / (chips * peaks["bf16_flops_per_s"]),
               nbytes / (chips * peaks["hbm_bytes_per_s"]))
