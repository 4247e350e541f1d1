"""The ops and bytes functions against shapes worked by hand.

    python -m pytest benchmark/tests/test_kernels.py
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from kernels import flash_attention, model_flops, paged_attention  # noqa: E402
from lib import peaks  # noqa: E402
from reference import gpt  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_flash_forward_by_hand():
    # b=1, n=1, s=4, d=2: QK^T is 2*4*4*2 = 64 FLOP, PV the same; causal half
    assert flash_attention.forward_flops(1, 1, 4, 2, causal=False) == 128
    assert flash_attention.forward_flops(1, 1, 4, 2) == 64
    # backward: four such products
    assert flash_attention.backward_flops(1, 1, 4, 2) == 128
    # q, k, v, out at 2 bytes: 4 * 8 * 2 = 64, plus 4 rows of float32 lse
    assert flash_attention.forward_bytes(1, 1, 4, 2) == 64 + 16
    assert flash_attention.backward_bytes(1, 1, 4, 2) == 128 + 16


def test_flash_train_step_at_gpt2_medium():
    sz = {"heads": 16, "hidden": 1024, "layers": 24}
    flops, nbytes = flash_attention.train_step(sz, 12, 1024)
    # per layer 6 causal products of 2*12*16*1024*1024*64 / 2 FLOP
    assert flops == 24 * 6 * (2 * 12 * 16 * 1024 * 1024 * 64) // 2
    assert nbytes == 24 * (12 * 12 * 16 * 1024 * 64 * 2
                           + 2 * 12 * 16 * 1024 * 4)
    # compute-bound by far
    assert peaks.roofline_seconds(flops, nbytes, PEAKS) == \
        flops / 197e12


def test_parameters_of_gpt2_medium():
    sz = {"hidden": 1024, "ffn": 4096, "layers": 24, "vocab_padded": 50304,
          "positions": 1024}
    # 12 h^2 + 13 h a layer, the padded token table, the final LayerNorm
    per_layer = 12 * 1024 * 1024 + 13 * 1024
    want = 24 * per_layer + 50304 * 1024 + 2 * 1024
    assert gpt.count_params(sz, positions=False) == want
    assert gpt.count_params(sz) == want + 1024 * 1024
    assert 353e6 < want < 355e6
    step = model_flops.train_step_flops(gpt, dict(sz, heads=16), 12, 1024)
    assert step == 6 * want * 12 * 1024 + flash_attention.train_step(
        dict(sz, heads=16), 12, 1024)[0]
    assert model_flops.decode_token_flops(gpt, sz) == 2 * want


def test_paged_decode_by_hand():
    # two slots with 3 and 5 tokens of history, 2 heads of 4, one layer:
    # 8 tokens * (k and v) * 2 heads * 4 = 128 elements of float32 cache
    flops, nbytes = paged_attention.decode([3, 5], 2, 2, 4, 1)
    assert flops == 4 * 2 * 4 * 8
    assert nbytes == 128 * 4 + 2 * 2 * 2 * 4 * 2
    # memory-bound
    assert peaks.roofline_seconds(flops, nbytes, PEAKS) == \
        nbytes / 819e9

