"""The ``deepseek-v2`` cell's own benchmark code at a tiny size on the CPU:
the latent driver run as the harness runs it, ``correct`` coming out true
for a sound run and false for the fp8 control and for each fault the timed
path can have, the new readers and kernel counts on hand-made views with
the numbers worked by hand, the traffic's lengths, and the configuration
file against the catalog's entry.

    python -m pytest benchmark/tests/test_deepseek_cell.py
"""

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
from drivers import deepseek_program, serve_hybrid, serve_latent  # noqa: E402
from drivers.serve import Tick                 # noqa: E402
from kernels import latent_attention           # noqa: E402
from lib import check                          # noqa: E402
from reference import deepseek_v2 as ref       # noqa: E402
import faults_deepseek                         # noqa: E402
from test_mimo_cell import (                   # noqa: E402,F401
    OTHER, PEAKS, read, ring, tick)

PRESETS = os.path.join(HERE, "tests", "presets_deepseek")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2 ** 31 + 11
REDUCED = ("num_hidden_layers", "n_routed_experts", "vocab_size")
CELL = "deepseek-v2.serve-long-context"


def run_tiny(seconds=0.4):
    import jax

    bench = harness.load_json(PRESETS, "BENCHMARK.json")
    bench["end_to_end"], bench["per_layer"] = [], []
    cell = harness.Cell(bench, "deepseek-tiny.serve", SEED, seconds, False,
                        jax.devices()[:1], root=PRESETS, data=PRESETS)
    out = cell.driver.run(cell)
    return check.passed(out["compared"]), out


def tiny():
    config = harness.load_json(PRESETS, "configs", "deepseek-tiny.json")
    limits = harness.load_json(PRESETS, "limits", "deepseek-tiny.serve.json")
    return ref.sizes_of(config), limits


# ------------------------------------------------------------------ correct


def test_sound_run_is_correct_and_reports_what_the_readers_take():
    ok, out = run_tiny()
    assert ok, out["compared"]
    obs = out["observed"]
    for key in ("window_s", "ticks", "tokens", "ticks_seen", "sizes",
                "memory", "block_size", "bytes_at_rest", "reference_s",
                "kv_latent_bytes_per_token"):
        assert key in obs
    assert isinstance(obs["ticks_seen"][0], Tick)
    assert obs["checked_requests"] == 2 and obs["checked_logit_rows"] > 0
    assert obs["preemptions"] == 0
    # 3 layers of 16 + 8 channels in one 128-lane tile, float32
    assert obs["kv_latent_bytes_per_token"] == 3 * 128 * 4
    assert set(out["end_to_end"]) == {"decode_tokens_per_s", "tpot_ms_p95"}


# what each planted fault fails: a choice of experts that is no near tie
# fails the margin (the reference has followed it, so the logits agree);
# any other fault fails every row
FAILS = {"group_limit_left_out": "router_choice_margin"}


@pytest.mark.parametrize("fault", sorted(faults_deepseek.FAULTS))
def test_fault_is_not_correct(fault):
    with faults_deepseek.FAULTS[fault]():
        ok, out = run_tiny()
    assert not ok, out["compared"]
    # the run itself was whole: what failed is the comparison
    compared = out["compared"]
    assert compared["requests_failed"][0] == 0
    assert compared["decode_compiles"][0] == compared["prefill_compiles"][0] \
        == 1
    failing = FAILS.get(fault, "logit_row_gap_max")
    assert compared[failing][0] > compared[failing][1]


@pytest.mark.parametrize("fault", [None] + sorted(faults_deepseek.FAULTS))
def test_control_and_reference_faults_fail_a_limit(fault):
    """The fp8 forward pass (and the reference with each fault planted) in
    the program's place, its expert choices followed as a program's are:
    told apart from the reference by the logits or by the margin."""
    sz, limits = tiny()
    w = ref.init_weights(deepseek_program.seed_key(SEED), sz)
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


def test_following_a_choice_reports_the_margin_at_both_levels():
    """A program that took the fourth expert for the third at one token
    (inside a kept group), and one that took an expert of a group the
    reference did not keep: followed, each with its margin."""
    sz, _ = tiny()
    w = ref.init_weights(deepseek_program.seed_key(SEED), sz)
    seq = np.random.default_rng(1).integers(0, sz["vocab"], 40).tolist()
    own = {}
    plain = np.asarray(ref.last_logits(w, [seq], sz, routing=[own]))
    assert own["margin"] == 0.0
    assert own["own"].shape == (sum(sz["experts"]), 256, sz["top_k"])
    size = sz["n_experts"] // sz["n_group"]
    kept = set((own["own"][0, 39] // size).tolist())
    assert len(kept) <= sz["topk_group"]
    inside = next(e for e in range(sz["n_experts"])
                  if e // size in kept and e not in own["own"][0, 39])
    outside = next(e for e in range(sz["n_experts"]) if e // size not in kept)
    margins = []
    for spare in (inside, outside):
        chosen = own["own"][:, :40].copy()
        chosen[0, 39, -1] = spare
        rec = {"chosen": chosen}
        moved = np.asarray(ref.last_logits(w, [seq], sz, routing=[rec]))
        margins.append(rec["margin"])
        assert rec["margin"] > 0.0
    assert serve_hybrid.row_gaps(moved, plain)[0] > 0.0
    # the reference's own choices, handed back, change nothing
    rec = {"chosen": own["own"][:, :40]}
    back = np.asarray(ref.last_logits(w, [seq], sz, routing=[rec]))
    np.testing.assert_array_equal(back, plain)
    assert rec["margin"] == 0.0


def test_reference_imports_nothing_of_the_program_and_is_expanded():
    with open(ref.__file__) as f:
        source = f.read()
    assert "import apex_tpu" not in source and "from apex_tpu" not in source
    assert 'precision=HIGHEST' in source
    # keys and values are made per head from the latent: no absorbed query
    assert '"sc,ncd->snd", c, uk' in source


def test_the_reference_pads_to_what_it_reads_not_to_max_seq():
    assert len(ref._padded(list(range(700)), 2048)) == 2048
    assert len(ref._padded(list(range(700)))) == 1024
    assert len(ref._padded(list(range(300)))) == 512
    assert serve_latent.PAD_STEP == 1024


# ------------------------------------------------------------ configuration


def test_configuration_equals_the_catalog_outside_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "DeepSeek-V2")
    config = harness.load_json(HERE, "configs", "deepseek-v2.json")
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    listed = next(c for c in bench["configs"] if c["name"] == "deepseek-v2")
    assert listed["source"] == entry["source_url"] == config["source"]
    assert sorted(listed["reduced"]) == sorted(REDUCED)
    for key, value in entry["config"].items():
        if key in REDUCED:
            assert config["source_values"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 20, 12800)
    # one routing group a chip, an eighth of the vocabulary
    assert config["source_values"]["n_routed_experts"] \
        // config["n_group"] == config["n_routed_experts"]
    assert config["share"] == {"chips_per_layer": 8, "experts_first": 0,
                               "vocab_ways": 8}


def test_benchmark_names_the_cell_and_its_metrics():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.workload(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2", "closed-long-context", 1)
    assert [m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")
            ] == ["decode_tokens_per_s", "setup_s"]
    per_layer = [m["name"] for m in harness.metrics_of(bench, CELL,
                                                       "per_layer")]
    assert per_layer == [
        "decode_tick_ms_p50", "decode_batch_occupancy", "decode_mfu",
        "decode_device_idle_share", "decode_host_ms_p50",
        "decode_call_ms_p50", "moe_time_share", "moe_roofline",
        "moe_expert_load_peak", "latent_decode_roofline",
        "latent_decode_time_share", "latent_cache_bytes_per_token"]
    limits = harness.load_json(HERE, "limits", CELL + ".json")
    assert sorted(limits) == ["logit_rms_gap", "logit_row_gap_max",
                              "router_choice_margin", "served_logit_gap"]


def test_parameter_counts_of_the_cut_and_of_the_whole():
    config = harness.load_json(HERE, "configs", "deepseek-v2.json")
    sz = ref.sizes_of(config)
    # ISSUE 33's table: 3,145.5 M stored (the norms' gains are 0.07 M more)
    assert ref.stored_params(sz) == pytest.approx(3.1455e9, rel=1e-3)
    # ISSUE 33's 1,198 M and the head's 65.5 M (``decode_mfu`` counts it)
    assert ref.count_params(sz) == pytest.approx(1.198e9 + 65.5e6, rel=1e-3)
    assert ref._attention_params(sz) == 149_225_472
    # the same count over the published 60 layers, 160 experts and whole
    # vocabulary is the published 236 B: the widths are read right
    src = config["source_values"]
    whole = dict(sz, layers=60, held=(0, 160), vocab=src["vocab_size"],
                 pattern=(0,) * 60, experts=(0,) + (1,) * 59)
    assert ref.stored_params(whole) == pytest.approx(235.7e9, rel=2e-3)
    assert ref.softmax_scale(sz) == pytest.approx(
        192 ** -0.5 * 1.2608 ** 2, rel=1e-4)


# ----------------------------------------------------------------- traffic


def test_no_session_ends_inside_a_window():
    mix = harness.load_json(HERE, "traffic", "closed-long-context.json")
    eng = mix["engine"]
    loop = serve_hybrid.LongAnswerLoop(mix, SEED, 12800)
    again = serve_hybrid.LongAnswerLoop(mix, SEED + 1, 12800)
    first = [loop.lengths(c, 0) for c in range(mix["callers"])]
    later = [loop.lengths(c, j) for c in range(mix["callers"])
             for j in (1, 2)]
    assert all(4096 <= p <= 12288 and 6144 <= a <= 8192 for p, a in first)
    assert all(64 <= p <= 128 and 2048 <= a <= 4096 for p, a in later)
    assert max(p + a for p, a in first + later) <= eng["max_seq"]
    assert sorted(first) == sorted(again.lengths(c, 0)
                                   for c in range(mix["callers"]))
    # every slot live, and room for 3,500 ticks of growth at the least
    assert mix["callers"] == eng["max_batch"] == 64
    held = sum(-(-p // eng["block_size"]) for p, _ in first)
    growth = (eng["n_blocks"] - held) * eng["block_size"] // 64
    assert growth >= 3500
    # at twice 25 ms a tick, 45 s are 3,600 tokens: under every answer
    assert min(a for _, a in first) > 2 * 45 / 0.025 / 2 + 12
    assert mix["driver"] == "serve_latent"


# ------------------------------------------------------------ kernel counts


def test_kernel_counts_by_hand():
    kind = {"heads": 128, "kv_rank": 512, "rope": 64, "k_dim": 192,
            "v_dim": 128}
    flops, nbytes = latent_attention.decode_rows(1000, kind, 5)
    assert nbytes == 5 * 1000 * 576 * 2
    assert flops == 5 * 1000 * 2 * 128 * (576 + 512)
    # 242 FLOP a byte against the v5e's ridge of 240
    assert flops / nbytes == pytest.approx(241.8, abs=0.1)
    assert latent_attention.expanded_bytes_per_token(kind, 5) == 409600


# ----------------------------------------------------------------- readers


LATENT_K = ('%paged_decode_latent.7 = bf16[64,128,512]{2,1,0} '
            'custom-call(%q), custom_call_target="tpu_custom_call"')
PREFILL_K = LATENT_K.replace("paged_decode_latent.7",
                             "paged_prefill_latent.3")


def latent_view(ops, **observed):
    config = harness.load_json(HERE, "configs", "deepseek-v2.json")
    device = {"busy_s": 4.0, "ops": ops, "collective_s": 0.0}
    seen = [Tick(10.06, 60.0, False, 64, []), Tick(11.06, 60.0, False, 64, [])]
    return {"trace": {"devices": [device], "busy_s": 4.0, "window_s": 5.0},
            "observed": dict(observed, ticks_seen=seen,
                             sizes=ref.sizes_of(config)),
            "chips": 1, "peaks": PEAKS}


def test_latent_readers_by_hand(ring):
    plan = dict(kv_tokens=600000, kv_pages=37500, kv_tokens_latent=600000,
                kv_pages_latent=37500)
    fetch = dict(moe_pairs=48, moe_experts_hit=40, moe_peak_pairs=4,
                 moe_group_tokens=96)
    ring.extend(tick(10, 10.0, plan, fetch))
    ring.extend(tick(20, 11.0, plan, fetch))
    view = latent_view({LATENT_K: [2.0, 10], PREFILL_K: [0.5, 1],
                        OTHER: [1.5, 2]}, kv_latent_bytes_per_token=6400.0)
    assert read("latent_decode_time_share", view) == pytest.approx(50.0)
    # 1.2 M rows a layer over two ticks, five layers: the products bind
    flops = 5 * 1200000 * 2 * 128 * 1088
    assert flops / 197e12 > 5 * 1200000 * 1152 / 819e9
    assert read("latent_decode_roofline", view) == pytest.approx(
        100 * (flops / 197e12) / 2.0)
    assert read("latent_cache_bytes_per_token", view) == 6400.0
    # the shared readers run on the same spans: 4 expert layers of 20
    assert read("moe_expert_load_peak", view) == pytest.approx(4 * 80 / 48)


def test_latent_readers_find_nothing_in_another_program(ring):
    """A program without the field, the kernel's name or the gauge (the
    parent of this PR; the other cells): nothing to read, never 0."""
    ring.extend(tick(10, 10.0, dict(kv_tokens=5, kv_pages=1), {}))
    view = latent_view({OTHER: [3.0, 2]})
    for name in ("latent_decode_roofline", "latent_decode_time_share",
                 "latent_cache_bytes_per_token"):
        assert read(name, view) is None, name
    # the kernel there but no field to count its rows by: still nothing
    view = latent_view({LATENT_K: [2.0, 10]})
    assert read("latent_decode_roofline", view) is None
    assert read("latent_decode_time_share", view) == pytest.approx(50.0)
