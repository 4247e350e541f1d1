"""Every per-layer reader on a hand-made view: the numbers worked by hand,
and nothing to read gives ``None``, never 0.

    python -m pytest benchmark/tests/test_metrics.py
"""

import importlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from drivers.serve import Tick          # noqa: E402
from reference import gpt               # noqa: E402

SZ = {"hidden": 1024, "heads": 16, "layers": 24, "ffn": 4096,
      "vocab_padded": 50304, "positions": 1024}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FLASH = ('%core_attention.52 = bf16[6,16,1024,64]{3,2,1,0} custom-call(%q), '
         'custom_call_target="tpu_custom_call"')
PAGED = ('%closed_call.9 = bf16[64,16,64]{2,1,0:T(8,128)(2,1)} custom-call('
         's32[64,64]{1,0} %t, f32[1536,16,16,64]{3,2,1,0} %k, '
         'f32[1536,16,16,64]{3,2,1,0} %v), '
         'custom_call_target="tpu_custom_call"')
OTHER = "%fusion.4 = bf16[8]{0} fusion(%p), kind=kLoop"
GATHER = "%ag = bf16[8]{0} all-gather-done(%s)"


def read(name, view):
    return importlib.import_module("metrics." + name).read(view)


def train_view(ops, chips=1):
    device = {"busy_s": 4.0, "ops": ops,
              "collective_s": ops.get(GATHER, [0.0, 0])[0]}
    return {"trace": {"devices": [device], "busy_s": 4.0, "window_s": 5.0},
            "observed": {"steps": 10, "window_s": 5.0, "sizes": SZ,
                         "step_ms": [400.0, 500.0, 600.0],
                         "memory": {"peak_bytes": [12e9, 11e9][:chips]}},
            "traffic": {"batch": 12, "seq": 1024}, "reference": gpt,
            "chips": chips, "peaks": PEAKS}


def test_training_readers_by_hand():
    view = train_view({FLASH: [1.0, 480], OTHER: [3.0, 99]})
    assert read("train_step_ms_p50", view) == 500.0
    assert read("train_device_idle_share", view) == pytest.approx(20.0)
    assert read("train_peak_hbm_gb", view) == 12.0
    assert read("flash_time_share", view) == pytest.approx(25.0)
    # 10 steps of causal attention: 6 * 24 * 2*12*16*1024^2*64 / 2 FLOP each
    least = 10 * 24 * 6 * (2 * 12 * 16 * 1024 * 1024 * 64) / 2 / 197e12
    assert read("flash_roofline", view) == pytest.approx(100 * least / 1.0)
    n = gpt.count_params(SZ, positions=False)
    flops = 6 * n * 12 * 1024 + least / 10 * 197e12
    assert read("train_mfu", view) == pytest.approx(
        100 * flops * 2.0 / 197e12)
    assert read("train_collective_share", view) is None     # one chip


def test_collective_share_on_four_chips():
    view = train_view({OTHER: [3.0, 9], GATHER: [1.0, 5]}, chips=4)
    assert read("train_collective_share", view) == pytest.approx(25.0)


def test_no_kernel_in_the_trace_reads_nothing():
    view = train_view({OTHER: [4.0, 9]})
    assert read("flash_time_share", view) is None
    assert read("flash_roofline", view) is None


def test_serving_readers_by_hand():
    ticks = [Tick(1.0, 300.0, False, 64, [100] * 64),
             Tick(2.0, 500.0, True, 63, [200] * 63),
             Tick(3.0, 310.0, False, 64, [])]
    device = {"busy_s": 4.0, "ops": {PAGED: [2.0, 48], OTHER: [2.0, 7]},
              "collective_s": 0.0}
    view = {"trace": {"devices": [device], "busy_s": 4.0, "window_s": 5.0},
            "observed": {"ticks_seen": ticks, "tokens": 1000, "window_s": 5.0,
                         "sizes": SZ, "block_size": 16},
            "traffic": {"engine": {"max_batch": 64, "n_blocks": 1536,
                                   "prefill_len": 256}},
            "reference": gpt, "chips": 1, "peaks": PEAKS}
    assert read("decode_tick_ms_p50", view) == 305.0
    assert read("decode_batch_occupancy", view) == pytest.approx(
        100 * 191 / 192)
    assert read("decode_device_idle_share", view) == pytest.approx(20.0)
    assert read("paged_decode_time_share", view) == pytest.approx(50.0)
    n = gpt.count_params(SZ, positions=False)
    assert read("decode_mfu", view) == pytest.approx(
        100 * 2 * n * 200.0 / 197e12)
    # live keys and values of both decode ticks, float32, 24 layers, plus
    # queries and outputs in bf16; memory-bound
    tokens = 6400 + 12600
    nbytes = 24 * (2 * tokens * 16 * 64 * 4) + 24 * 2 * 127 * 16 * 64 * 2
    assert read("paged_decode_roofline", view) == pytest.approx(
        100 * (nbytes / 819e9) / 2.0)
    device["ops"] = {OTHER: [4.0, 7]}
    assert read("paged_decode_roofline", view) is None
    assert read("paged_decode_time_share", view) is None
