"""The ``mimo-v2-flash`` cell's own benchmark code at a tiny size on the CPU:
the hybrid driver run as the harness runs it, ``correct`` coming out true
for a sound run and false for the fp8 control and for each fault the timed
path can have, the new readers and kernel counts on hand-made views with
the numbers worked by hand, the traffic's lengths, and the configuration
file against the catalog's entry.

    python -m pytest benchmark/tests/test_mimo_cell.py
"""

import collections
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
from apex_tpu.observability import spans       # noqa: E402
from drivers import mimo_program, serve_hybrid  # noqa: E402
from drivers.serve import Tick                 # noqa: E402
from kernels import moe, paged_attention_groups  # noqa: E402
from lib import check                          # noqa: E402
from reference import mimo_v2_flash as ref     # noqa: E402
import faults_mimo                             # noqa: E402

PRESETS = os.path.join(HERE, "tests", "presets_mimo")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2 ** 31 + 11
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ("num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size")


def run_tiny(seconds=0.4):
    import jax

    bench = harness.load_json(PRESETS, "BENCHMARK.json")
    bench["end_to_end"], bench["per_layer"] = [], []
    cell = harness.Cell(bench, "mimo-tiny.serve", SEED, seconds, False,
                        jax.devices()[:1], root=PRESETS, data=PRESETS)
    out = cell.driver.run(cell)
    return check.passed(out["compared"]), out


def tiny():
    config = harness.load_json(PRESETS, "configs", "mimo-tiny.json")
    limits = harness.load_json(PRESETS, "limits", "mimo-tiny.serve.json")
    return ref.sizes_of(config), limits


# ------------------------------------------------------------------ correct


def test_sound_run_is_correct_and_reports_what_the_readers_take():
    ok, out = run_tiny()
    assert ok, out["compared"]
    obs = out["observed"]
    for key in ("window_s", "ticks", "tokens", "ticks_seen", "sizes",
                "memory", "block_size", "bytes_at_rest", "reference_s"):
        assert key in obs
    assert isinstance(obs["ticks_seen"][0], Tick)
    assert obs["checked_requests"] == 3 and obs["checked_logit_rows"] > 0
    assert set(out["end_to_end"]) == {"decode_tokens_per_s", "tpot_ms_p95"}


@pytest.mark.parametrize("fault", sorted(faults_mimo.FAULTS))
def test_fault_is_not_correct(fault):
    with faults_mimo.FAULTS[fault]():
        ok, out = run_tiny()
    assert not ok, out["compared"]
    # the run itself was whole: what failed is the comparison of the logits
    compared = out["compared"]
    assert compared["requests_failed"][0] == 0
    assert compared["decode_compiles"][0] == compared["prefill_compiles"][0] \
        == 1
    # a choice of experts that is no near tie fails the margin (the
    # reference has followed it, so the logits agree); any other fault
    # fails every row
    failing = ("router_choice_margin" if fault == "selection_bias_left_out"
               else "logit_row_gap_max")
    assert compared[failing][0] > compared[failing][1]


def test_altered_token_is_not_correct():
    import faults

    with faults.token_altered(every=7, vocab=tiny()[0]["vocab"]):
        ok, out = run_tiny()
    compared = out["compared"]
    assert not ok
    assert compared["served_logit_gap"][0] > compared["served_logit_gap"][1]


@pytest.mark.parametrize("fault", [None] + sorted(faults_mimo.FAULTS))
def test_control_and_reference_faults_fail_a_limit(fault):
    """The fp8 forward pass (and the reference with each fault planted) in
    the program's place, its expert choices followed as a program's are:
    told apart from the reference by the logits or by the margin."""
    sz, limits = tiny()
    w = ref.init_weights(mimo_program.seed_key(SEED), sz)
    rng = np.random.default_rng(0)
    sequences = [rng.integers(0, sz["vocab"], n).tolist()
                 for n in (12, 20, 28, 36)]
    theirs = [{} for _ in sequences]
    got = ref.last_logits(w, sequences, sz, None,
                          ref.FP8 if fault is None else None, fault, theirs)
    ours = [{"chosen": r["own"][:, :len(seq)]}
            for r, seq in zip(theirs, sequences)]
    want = ref.last_logits(w, sequences, sz, routing=ours)
    values = serve_hybrid.numbers(np.asarray(got), np.asarray(want),
                                  max(r["margin"] for r in ours), 0.0)
    assert not check.passed(serve_hybrid.held(values, limits)), values


def test_following_a_near_tie_moves_the_row_and_reports_the_margin():
    """A program that took the ninth expert for the eighth at one token:
    followed, the reference's row is the program's; not followed, it is
    an expert's output away."""
    sz, _ = tiny()
    w = ref.init_weights(mimo_program.seed_key(SEED), sz)
    seq = np.random.default_rng(1).integers(0, sz["vocab"], 40).tolist()
    own = {}
    plain = np.asarray(ref.last_logits(w, [seq], sz, routing=[own]))
    assert own["margin"] == 0.0
    assert own["own"].shape == (sum(sz["experts"]), 256, sz["top_k"])
    chosen = own["own"][:, :40].copy()
    # the last token's last choice in the first expert layer becomes an
    # expert it did not choose
    spare = next(e for e in range(sz["n_experts"])
                 if e not in chosen[0, 39])
    chosen[0, 39, -1] = spare
    rec = {"chosen": chosen}
    moved = np.asarray(ref.last_logits(w, [seq], sz, routing=[rec]))
    assert rec["margin"] > 0.0
    assert serve_hybrid.row_gaps(moved, plain)[0] > 1e-3
    # the reference's own choices, handed back, change nothing
    rec = {"chosen": own["own"][:, :40]}
    back = np.asarray(ref.last_logits(w, [seq], sz, routing=[rec]))
    np.testing.assert_array_equal(back, plain)
    assert rec["margin"] == 0.0


def test_rounding_to_the_stated_precision_flips_few_choices():
    """bfloat16 GEMM operands change a few of the expert layers' choices,
    each by a near tie."""
    sz, _ = tiny()
    w = ref.init_weights(mimo_program.seed_key(SEED), sz)
    seq = np.random.default_rng(1).integers(0, sz["vocab"], 40).tolist()
    sound, rounded = {}, {}
    ref.last_logits(w, [seq], sz, routing=[sound])
    ref.last_logits(w, [seq], sz, None, ref.BF16, routing=[rounded])
    a, b = sound["own"][:, :40], rounded["own"][:, :40]
    assert a.shape == b.shape == (sum(sz["experts"]), 40, sz["top_k"])
    changed = int(np.sum(np.sort(a, -1) != np.sort(b, -1)))
    assert changed < 0.2 * a.size
    follow = {"chosen": b}
    ref.last_logits(w, [seq], sz, routing=[follow])
    assert follow["margin"] < 0.02


def test_choices_by_request_places_every_call():
    """Rows of a prefill call and of decode calls land at their positions;
    a later call replaces an earlier one; what no call says stays -1."""
    prefill = np.arange(2 * 8 * 2).reshape(2, 8, 2)       # 2 slots x 4 rows
    decode = 100 + np.arange(2 * 2 * 2).reshape(2, 2, 2)
    routed = [(prefill, ((7, 4, 0, 3), (9, 0, 2, 4))),
              (decode, ((7, 1, 3, 1), (9, 0, 6, 1))),
              (decode + 50, ((7, 1, 3, 1),))]
    out = serve_hybrid.choices_by_request(routed, {7: 5, 9: 6})
    assert set(out) == {7, 9} and out[7].shape == (2, 5, 2)
    np.testing.assert_array_equal(out[7][:, :3], prefill[:, 4:7])
    np.testing.assert_array_equal(out[7][:, 3], decode[:, 1] + 50)
    assert (out[7][:, 4] == -1).all()
    assert (out[9][:, :2] == -1).all()
    np.testing.assert_array_equal(out[9][:, 2:6], prefill[:, 0:4])


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
                     if e["name"] == "MiMo-V2-Flash")
    config = harness.load_json(HERE, "configs", "mimo-v2-flash.json")
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    listed = next(c for c in bench["configs"] if c["name"] == "mimo-v2-flash")
    assert listed["source"] == entry["source_url"] == config["source"]
    assert sorted(listed["reduced"]) == sorted(REDUCED)
    for key, value in entry["config"].items():
        if key in REDUCED:
            assert config["source_values"][key] == value
        else:
            assert config[key] == value, key
    assert config["hybrid_layer_pattern"] == \
        entry["config"]["hybrid_layer_pattern"][:7]
    assert config["moe_layer_freq"] == entry["config"]["moe_layer_freq"][:7]


def test_parameter_counts_of_the_cut_and_of_the_whole():
    config = harness.load_json(HERE, "configs", "mimo-v2-flash.json")
    sz = ref.sizes_of(config)
    assert ref.count_params(sz) == pytest.approx(1.011e9, rel=2e-3)
    assert ref.stored_params(sz) == pytest.approx(3.430e9, rel=1e-3)
    # the same count over the published 48 layers, 256 experts and whole
    # vocabulary is the published 309 B: the widths are read right
    src = config["source_values"]
    whole = dict(sz, layers=48, held=(0, 256), vocab=src["vocab_size"],
                 pattern=tuple(src["hybrid_layer_pattern"]),
                 experts=tuple(src["moe_layer_freq"]))
    assert ref.stored_params(whole) == pytest.approx(308.8e9, rel=2e-3)
    assert sz["rotary"] == 64 and sz["kinds"][1]["window"] == 128
    assert [k["kv_heads"] for k in sz["kinds"]] == [4, 8]


# ----------------------------------------------------------------- traffic


def test_first_request_draws_from_its_own_ranges():
    mix = harness.load_json(HERE, "traffic", "closed-long-answer.json")
    loop = serve_hybrid.LongAnswerLoop(mix, SEED, 19072)
    again = serve_hybrid.LongAnswerLoop(mix, SEED + 1, 19072)
    first = [loop.lengths(c, 0) for c in range(mix["callers"])]
    later = [loop.lengths(c, j) for c in range(mix["callers"])
             for j in (1, 2)]
    assert all(128 <= p <= 3072 and 8 <= a <= 3072 for p, a in first)
    assert all(64 <= p <= 128 and 2048 <= a <= 4096 for p, a in later)
    assert max(p + a for p, a in first + later) <= mix["engine"]["max_seq"]
    # every seed meets the same lengths, dealt to other callers
    assert sorted(first) == sorted(again.lengths(c, 0)
                                   for c in range(mix["callers"]))
    prompt, n_answer, _ = loop.request(3, 1)
    assert len(prompt) == loop.lengths(3, 1)[0] and prompt.max() < 19072
    assert "window_blocks" not in mix["engine"]     # the engine derives it


# ------------------------------------------------------------ kernel counts


def test_kernel_counts_by_hand():
    flops, nbytes = moe.routed(100, 14, 4096, 2048)
    assert flops == 100 * 6 * 4096 * 2048
    assert nbytes == 14 * 3 * 4096 * 2048 * 2 + 2 * 100 * 4096 * 2
    assert moe.expert_bytes(4096, 2048) == 50331648
    kind = {"heads": 64, "kv_heads": 8, "k_dim": 192, "v_dim": 128}
    flops, nbytes = paged_attention_groups.decode_rows(1000, kind, 5)
    assert nbytes == 5 * 1000 * 8 * 320 * 2
    assert flops == 5 * 1000 * 2 * 64 * 320


# ----------------------------------------------------------------- readers


def read(name, view):
    return importlib.import_module("metrics." + name).read(view)


def made(name, start, end, ident, parent=0, **fields):
    s = spans.span(name, **fields)
    s.start, s.end, s.id, s.parent = start, end, ident, parent
    return s


def tick(ident, start, plan, fetch, prefill=None):
    phases = [("decode_plan", plan), ("decode_dispatch", {}),
              ("decode_fetch", fetch)]
    if prefill is not None:
        phases = [("prefill_dispatch", {}), ("prefill_fetch", prefill)] \
            + phases
    out, at = [], start + 0.001
    for i, (phase, fields) in enumerate(phases):
        out.append(made("serving/tick/" + phase, at, at + 0.01,
                        ident + 1 + i, parent=ident, **fields))
        at += 0.011
    return out + [made("serving/tick", start, at, ident)]


@pytest.fixture
def ring(monkeypatch):
    made_ring = collections.deque(maxlen=64)
    monkeypatch.setattr(spans, "_RING", made_ring)
    return made_ring


MOE_UP = ('%moe_experts.20 = bf16[512,4096]{1,0} custom-call(%a, %b), '
          'custom_call_target="tpu_custom_call"')
MOE_DOWN = MOE_UP.replace("moe_experts.20", "moe_experts.21")
WINDOW_K = ('%paged_decode_window.7 = bf16[64,64,128]{2,1,0} custom-call(%q), '
            'custom_call_target="tpu_custom_call"')
FULL_K = WINDOW_K.replace("paged_decode_window.7", "paged_decode_full.2")
OTHER = "%fusion.47 = f32[1220608]{0} fusion(%p), kind=kCustom"


def hybrid_view(ops):
    config = harness.load_json(HERE, "configs", "mimo-v2-flash.json")
    device = {"busy_s": 4.0, "ops": ops, "collective_s": 0.0}
    seen = [Tick(10.06, 60.0, False, 64, []), Tick(11.06, 60.0, False, 64, [])]
    return {"trace": {"devices": [device], "busy_s": 4.0, "window_s": 5.0},
            "observed": {"ticks_seen": seen, "sizes": ref.sizes_of(config)},
            "chips": 1, "peaks": PEAKS}


def test_hybrid_readers_by_hand(ring):
    plan = dict(kv_tokens=128000, kv_pages=8000, kv_tokens_full=128000,
                kv_pages_full=8000, kv_tokens_window=8192,
                kv_pages_window=576, window_blocks_held=600,
                window_blocks_freed=3)
    ring.extend(tick(10, 10.0, plan, dict(moe_pairs=200, moe_experts_hit=80,
                                          moe_peak_pairs=8)))
    ring.extend(tick(20, 11.0, plan,
                     dict(moe_pairs=100, moe_experts_hit=60,
                          moe_peak_pairs=5),
                     prefill=dict(moe_pairs=50, moe_experts_hit=30,
                                  moe_peak_pairs=4)))
    view = hybrid_view({MOE_UP: [0.2, 12], MOE_DOWN: [0.1, 12],
                        WINDOW_K: [0.05, 10], FULL_K: [0.5, 4],
                        OTHER: [3.0, 2]})
    assert read("moe_time_share", view) == pytest.approx(100 * 0.3 / 4.0)
    nbytes = 170 * 50331648 + 2 * 350 * 4096 * 2
    assert read("moe_roofline", view) == pytest.approx(
        100 * (nbytes / 819e9) / 0.3)
    # 96 (layer, expert) entries: 8 over 200/96 and 5 over 100/96
    assert read("moe_expert_load_peak", view) == pytest.approx(
        (8 * 96 / 200 + 5 * 96 / 100) / 2)
    assert read("window_decode_roofline", view) == pytest.approx(
        100 * (5 * 16384 * 8 * 320 * 2 / 819e9) / 0.05)
    assert read("full_decode_roofline", view) == pytest.approx(
        100 * (2 * 256000 * 4 * 320 * 2 / 819e9) / 0.5)
    assert read("kv_window_block_share", view) == pytest.approx(
        100 * 1200 / 16000)


def test_hybrid_readers_find_nothing_in_another_program(ring):
    """A program without the spans' fields or the kernels' names (the
    parent of this PR; the GPT cells): nothing to read, never 0."""
    ring.extend(tick(10, 10.0, dict(kv_tokens=5, kv_pages=1), {}))
    view = hybrid_view({OTHER: [3.0, 2]})
    for name in ("moe_time_share", "moe_roofline", "moe_expert_load_peak",
                 "window_decode_roofline", "full_decode_roofline",
                 "kv_window_block_share"):
        assert read(name, view) is None, name
    # the kernels there but no field to count their rows by: still nothing
    view = hybrid_view({WINDOW_K: [0.05, 10], MOE_UP: [0.2, 12]})
    assert read("window_decode_roofline", view) is None
    assert read("moe_roofline", view) is None
    assert read("moe_time_share", view) == pytest.approx(5.0)
