"""``correct`` has to come out false when it should: for the control (the
plain reference computed in fp8, the nearest precision below the
configuration's bfloat16, put in the program's place) and for each fault
the timed path can have, planted underneath a whole run of the harness.
Tiny presets on the CPU; the harness's look for a chip is skipped, the rest
of a run is driven as it is.

    python -m pytest benchmark/tests/test_correct.py
"""

import os
import sys

# four virtual devices for the four-chip layout, before JAX starts
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)
sys.path.insert(2, os.path.join(HERE, "tests"))

import run as harness                    # noqa: E402
from lib import check                    # noqa: E402
from reference import gpt                # noqa: E402
import faults                            # noqa: E402

PRESETS = os.path.join(HERE, "tests", "presets")
SEED = 2 ** 31 + 11


def run_tiny(name, seconds=0.5):
    import jax

    bench = harness.load_json(PRESETS, "BENCHMARK.json")
    full = harness.load_json(ROOT, "BENCHMARK.json")
    bench["end_to_end"], bench["per_layer"] = full["end_to_end"], []
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == name)
    cell = harness.Cell(bench, name, SEED, seconds, False,
                        jax.devices()[:chips], root=PRESETS, data=PRESETS)
    out = cell.driver.run(cell)
    return check.passed(out["compared"]), out["compared"]


def tiny(kind):
    config = harness.load_json(PRESETS, "configs", "gpt-tiny.json")
    traffic = harness.load_json(PRESETS, "traffic", kind + ".json")
    limits = harness.load_json(
        PRESETS, "limits",
        "gpt-tiny.train.json" if kind == "train-tiny" else "gpt-tiny.serve.json")
    return gpt.sizes_of(config), traffic, limits


@pytest.mark.parametrize("name", ["gpt-tiny.train", "gpt-tiny-erf.train",
                                  "gpt-tiny.train-pp2tp2", "gpt-tiny.serve"])
def test_sound_run_is_correct(name):
    ok, compared = run_tiny(name)
    assert ok, compared


def test_reference_takes_its_gelu_from_the_configuration():
    """``gelu_new`` is the tanh form and ``gelu`` the exact one; a program
    that runs the one where the configuration states the other is held to
    the stated one."""
    import jax

    x = jax.numpy.linspace(-4.0, 4.0, 101)
    np.testing.assert_allclose(gpt._gelu(x, "gelu_new"),
                               jax.nn.gelu(x, approximate=True), atol=1e-6)
    np.testing.assert_allclose(gpt._gelu(x, "gelu"),
                               jax.nn.gelu(x, approximate=False), atol=1e-6)
    assert float(np.max(np.abs(gpt._gelu(x, "gelu")
                               - gpt._gelu(x, "gelu_new")))) > 1e-4
    with pytest.raises(ValueError):
        gpt._gelu(x, "relu")
    for name, kind in (("gpt-tiny.json", "gelu_new"),
                       ("gpt-tiny-erf.json", "gelu")):
        config = harness.load_json(PRESETS, "configs", name)
        assert gpt.sizes_of(config)["gelu"] == kind


def test_training_control_in_fp8_is_not_correct():
    import jax

    from drivers import gpt_program, train

    sz, traffic, limits = tiny("train-tiny")
    w0 = gpt.init_weights(gpt_program.seed_key(SEED), sz)
    batches = [jax.numpy.asarray(train.batch_of(SEED, i, traffic, sz["vocab"]))
               for i in range(3)]
    ref = gpt.train(w0, batches, sz, traffic["adam"], 2)
    control = gpt.train(w0, batches, sz, traffic["adam"], 2, quant=gpt.FP8)
    compared = check.training(control, ref, limits)
    assert all(np.isfinite(v) for v, _ in compared.values()), compared
    assert not check.passed(compared), compared
    # and the reference against itself passes, exactly
    assert check.passed(check.training(ref, ref, limits))


@pytest.mark.parametrize("fault, cell", [
    ("state_unchanged", "gpt-tiny.train"),
    ("half_batch", "gpt-tiny.train"),
    ("exchange_left_out", "gpt-tiny.train-pp2tp2"),
    ("token_altered", "gpt-tiny.serve"),
])
def test_fault_is_not_correct(fault, cell):
    import jax

    if cell.endswith("pp2tp2") and len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    with faults.FAULTS[fault]():
        ok, compared = run_tiny(cell)
    assert not ok, compared


def test_serving_control_in_fp8_is_not_correct():
    """The fp8 forward pass's logits against the reference's, on sequences
    of the mix's lengths: the number that tells the precisions apart (the
    served tokens alone do not: both agree with the reference's first
    choice but at rare near-ties)."""
    from drivers import gpt_program

    sz, mix, limits = tiny("closed-tiny")
    w = gpt.init_weights(gpt_program.seed_key(SEED), sz)
    rng = np.random.default_rng(0)
    pad = mix["prompt_tokens"][1] + mix["answer_tokens"][1]
    sequences = [rng.integers(0, sz["vocab"], n).tolist()
                 for n in (12, 20, 28, 36)]
    want = gpt.last_logits(w, sequences, sz, pad)
    control = gpt.last_logits(w, sequences, sz, pad, gpt.FP8)
    gap = check.logit_rms_gap(control, want)
    assert np.isfinite(gap) and gap > limits["logit_rms_gap"]
    assert check.logit_rms_gap(want, want) == 0.0
