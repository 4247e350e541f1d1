"""The ``trinity-mini`` cell's own benchmark code at a tiny size on the CPU:
the hybrid training driver run as the harness runs it, ``correct`` coming
out true for a sound run and false for each fault the timed path can have
and for the fp8 control, the new readers and kernel counts on hand-made
views with the numbers worked by hand, and the configuration file against
the catalog's entry.

    python -m pytest benchmark/tests/test_trinity_cell.py
"""

import importlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)
sys.path.insert(2, os.path.join(HERE, "tests"))

import run as harness                          # noqa: E402
from drivers import train_hybrid, trinity_program  # noqa: E402
from kernels import hybrid_attention, hybrid_model_flops, moe_train  # noqa: E402
from lib import check                          # noqa: E402
from reference import trinity_mini as ref      # noqa: E402
import faults_trinity                          # noqa: E402

PRESETS = os.path.join(HERE, "tests", "presets_trinity")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2 ** 31 + 11
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ("num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts", "vocab_size")


def run_tiny(seconds=0.05):
    import jax

    bench = harness.load_json(PRESETS, "BENCHMARK.json")
    bench["end_to_end"], bench["per_layer"] = [], []
    cell = harness.Cell(bench, "trinity-tiny.train", SEED, seconds, False,
                        jax.devices()[:1], root=PRESETS, data=PRESETS)
    out = cell.driver.run(cell)
    return check.passed(out["compared"]), out


def tiny():
    config = harness.load_json(PRESETS, "configs", "trinity-tiny.json")
    limits = harness.load_json(PRESETS, "limits", "trinity-tiny.train.json")
    traffic = harness.load_json(PRESETS, "traffic", "train-tiny-hybrid.json")
    return ref.sizes_of(config), limits, traffic


# ------------------------------------------------------------------ correct


def test_sound_run_is_correct_and_reports_what_the_readers_take():
    ok, out = run_tiny()
    assert ok, out["compared"]
    obs = out["observed"]
    for key in ("window_s", "steps", "step_ms", "sizes", "memory",
                "moe_pairs", "reference_s", "choices_flipped"):
        assert key in obs
    assert obs["moe_pairs"].shape == (obs["steps"], 2, 4, 4)
    assert set(out["end_to_end"]) == {"train_tokens_per_s"}
    assert set(out["compared"]) == {
        "grad_norm_gap", "grad_norm_gap_median", "change_norm_gap",
        "change_norm_gap_median", "router_choice_margin", "skipped_steps",
        "nonfinite_steps"}


@pytest.mark.parametrize("fault", sorted(faults_trinity.FAULTS))
def test_fault_is_not_correct(fault):
    with faults_trinity.FAULTS[fault]():
        ok, out = run_tiny()
    assert not ok, out["compared"]
    compared = out["compared"]
    # the run itself was whole: what failed is a comparison
    assert compared["skipped_steps"][0] == compared["nonfinite_steps"][0] == 0


def test_fp8_control_is_not_correct():
    """The reference with its layer GEMMs in fp8 in the program's place,
    its own expert choices followed: told apart by a gap or by the margin."""
    import jax.numpy as jnp

    sz, limits, traffic = tiny()
    key = trinity_program.seed_key(SEED)
    batches = [jnp.asarray(train_hybrid.batch_of(SEED, s, traffic,
                                                 sz["vocab"]))
               for s in range(2)]
    low = ref.train(key, batches, sz, traffic["adam"], quant=ref.FP8)
    want = ref.train(key, batches, sz, traffic["adam"], chosen=low["own"])
    compared = check.training(low, want, limits)
    compared["router_choice_margin"] = (want["router_choice_margin"],
                                        limits["router_choice_margin"])
    assert not check.passed(compared), compared
    # the reference's own choices handed back change nothing
    plain = ref.train(key, batches[:1], sz, traffic["adam"])
    again = ref.train(key, batches[:1], sz, traffic["adam"],
                      chosen=plain["own"])
    assert again["grad_norms"] == plain["grad_norms"]
    assert again["router_choice_margin"] == 0.0
    assert again["choices_flipped"] == 0.0


def test_choices_by_sequence_puts_a_row_a_sequence():
    m, layers, mb, s, k = 2, 3, 2, 5, 4
    chosen = np.arange(m * layers * mb * s * k).reshape(m, layers, mb * s, k)
    out = train_hybrid.choices_by_sequence(chosen, m * mb, s)
    assert out.shape == (m * mb, layers, s, k)
    np.testing.assert_array_equal(out[3, 1], chosen[1, 1, s:])
    np.testing.assert_array_equal(out[0, 2], chosen[0, 2, :s])


def test_reference_band_of_keys_is_the_whole_mask(monkeypatch):
    """A sliding layer's blocks of query rows meet only the band of keys
    their window reaches (at the cell's size; at the tiny one a block holds
    the sequence): the same output as every key under the mask."""
    import jax

    sz, _, _ = tiny()
    lw = ref.init_weights(jax.random.PRNGKey(0), sz)["layers"][0]
    a = jax.random.normal(jax.random.PRNGKey(1), (128, sz["hidden"]))
    whole = ref.attention(a, lw, True, sz)
    monkeypatch.setattr(ref, "ROW_BLOCK", 32)       # 24 + 32 < 128: a band
    np.testing.assert_allclose(np.asarray(ref.attention(a, lw, True, sz)),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)


def test_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        source = f.read()
    assert "import apex_tpu" not in source and "from apex_tpu" not in source
    assert 'precision=HIGHEST' in source


# ------------------------------------------------------------ configuration


def test_configuration_equals_the_catalog_outside_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Trinity-Mini")
    config = harness.load_json(HERE, "configs", "trinity-mini.json")
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    listed = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert listed["source"] == entry["source_url"] == config["source"]
    assert sorted(listed["reduced"]) == sorted(REDUCED)
    for key, value in entry["config"].items():
        if key in REDUCED:
            assert config["source_values"][key] == value
        else:
            assert config[key] == value, key
    # published layers 1 and 4-7: one dense sliding layer, one whole period
    kept = config["share"]["layers"]
    assert kept == [1, 4, 5, 6, 7]
    assert config["layer_types"] == [entry["config"]["layer_types"][i]
                                     for i in kept]
    assert config["num_dense_layers"] == sum(
        i < entry["config"]["num_dense_layers"] for i in kept)
    assert config["num_experts"] * config["share"]["chips_per_layer"] == \
        entry["config"]["num_experts"]
    assert config["vocab_size"] * config["share"]["vocab_ways"] == \
        entry["config"]["vocab_size"]
    assert config["assumed"]["padded_vocab_size"] % 128 == 0


def test_parameter_counts_of_the_cut_and_of_the_whole():
    config = harness.load_json(HERE, "configs", "trinity-mini.json")
    sz = ref.sizes_of(config)
    assert ref.count_params(sz) == pytest.approx(276.8e6, rel=1e-3)
    assert ref.stored_params(sz) == pytest.approx(705.7e6, rel=1e-3)
    # the same count over the published 32 layers, 128 experts and whole
    # vocabulary is the published 26 B: the widths are read right
    src = config["source_values"]
    whole = dict(sz, layers=32, dense_layers=2, held=(0, 128),
                 vocab_padded=src["vocab_size"])
    assert ref.stored_params(whole) == pytest.approx(26.1e9, rel=0.01)
    assert sz["window"] == 2048 and sz["sliding"] == (True,) * 4 + (False,)


# ------------------------------------------------------------------ readers


def read(name, view):
    return importlib.import_module("metrics." + name).read(view)


def kernel(name, number):
    return (f'%{name}.{number} = bf16[1,32,8192,128]{{3,2,1,0}} '
            'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call"')


OTHER = "%fusion.47 = f32[1220608]{0} fusion(%p), kind=kCustom"


def train_view(ops, pairs):
    config = harness.load_json(HERE, "configs", "trinity-mini.json")
    device = {"busy_s": 4.0, "ops": ops, "collective_s": 0.0}
    return {"trace": {"devices": [device], "busy_s": 4.0, "window_s": 4.1},
            "observed": {"steps": 8, "window_s": 4.1, "moe_pairs": pairs,
                         "sizes": ref.sizes_of(config)},
            "traffic": {"batch": 2, "seq": 8192}, "chips": 1, "peaks": PEAKS}


def test_hybrid_train_readers_by_hand():
    pairs = np.full((8, 2, 4, 16), 512, np.int64)
    pairs[:, :, :, 0] = 1024
    pairs[:, :, :, 1] = 0
    view = train_view({
        kernel("flash_window", 3): [0.30, 64], kernel("flash_window", 4):
        [0.30, 64], kernel("flash_full", 5): [0.25, 32],
        kernel("moe_experts", 7): [0.10, 128],
        kernel("moe_experts_bwd", 9): [0.14, 128], OTHER: [2.0, 8]}, pairs)
    sz = view["observed"]["sizes"]
    assert read("window_flash_time_share", view) == pytest.approx(15.0)
    assert read("full_flash_time_share", view) == pytest.approx(6.25)
    assert read("moe_train_time_share", view) == pytest.approx(6.0)
    flops, nbytes = hybrid_attention.train_step(sz, True, 2, 8192)
    assert flops / 197e12 > nbytes / 819e9          # compute bounds it
    assert read("window_flash_roofline", view) == pytest.approx(
        100 * 8 * flops / 197e12 / 0.60)
    flops, _ = hybrid_attention.train_step(sz, False, 2, 8192)
    assert read("full_flash_roofline", view) == pytest.approx(
        100 * 8 * flops / 197e12 / 0.25)
    total, hit = int(pairs.sum()), int((pairs > 0).sum())
    flops, nbytes = moe_train.routed(total, hit, 2048, 1024)
    assert read("moe_train_roofline", view) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 0.24)
    assert read("moe_train_expert_load_peak", view) == pytest.approx(2.0)
    step = hybrid_model_flops.train_step_flops(sz, 2, 8192, total / 8)
    assert read("hybrid_train_mfu", view) == pytest.approx(
        100 * step * 8 / 4.1 / 197e12)


def test_hybrid_train_readers_find_nothing_in_another_program():
    """A program without the kernels' names or the pairs (the GPT cells):
    nothing to read, never 0."""
    view = train_view({OTHER: [3.0, 2]}, None)
    for name in ("hybrid_train_mfu", "window_flash_roofline",
                 "full_flash_roofline", "window_flash_time_share",
                 "full_flash_time_share", "moe_train_roofline",
                 "moe_train_time_share", "moe_train_expert_load_peak"):
        assert read(name, view) is None, name
