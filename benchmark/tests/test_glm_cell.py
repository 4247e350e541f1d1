"""The ``glm-5`` cell's own benchmark code at a tiny size on the CPU: the
sparse driver run as the harness runs it, ``correct`` coming out true for a
sound run and false for the fp8 control and for each fault the timed path
can have (but the one no output shows), the new readers and kernel counts
on hand-made views with the numbers worked by hand, the traffic's lengths,
and the configuration file against the catalog's entry.

    python -m pytest benchmark/tests/test_glm_cell.py
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
from drivers import glm_program, serve_hybrid, serve_sparse  # noqa: E402
from drivers.serve import Tick                 # noqa: E402
from kernels import sparse_attention           # noqa: E402
from lib import check                          # noqa: E402
from reference import glm_5 as ref             # noqa: E402
import faults_glm                              # noqa: E402
from test_mimo_cell import (                   # noqa: E402,F401
    OTHER, PEAKS, read, ring, tick)

PRESETS = os.path.join(HERE, "tests", "presets_glm")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2 ** 31 + 11
REDUCED = ("num_hidden_layers", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers")
CELL = "glm-5.serve-sparse-long-context"
NEW = ("index_scan_roofline", "index_select_roofline",
       "sparse_decode_roofline", "sparse_attention_time_share",
       "index_selected_share")


def run_tiny(seconds=0.4):
    import jax

    bench = harness.load_json(PRESETS, "BENCHMARK.json")
    bench["end_to_end"], bench["per_layer"] = [], []
    cell = harness.Cell(bench, "glm-tiny.serve", SEED, seconds, False,
                        jax.devices()[:1], root=PRESETS, data=PRESETS)
    out = cell.driver.run(cell)
    return check.passed(out["compared"]), out


def tiny():
    config = harness.load_json(PRESETS, "configs", "glm-tiny.json")
    limits = harness.load_json(PRESETS, "limits", "glm-tiny.serve.json")
    return ref.sizes_of(config), limits


# ------------------------------------------------------------------ correct


def test_sound_run_is_correct_and_reports_what_the_readers_take():
    ok, out = run_tiny()
    assert ok, out["compared"]
    obs = out["observed"]
    for key in ("window_s", "ticks", "tokens", "ticks_seen", "sizes",
                "memory", "block_size", "bytes_at_rest", "reference_s",
                "kv_latent_bytes_per_token", "dsa_rows_scanned",
                "dsa_rows_selected", "dsa_prefill_rows_scanned"):
        assert key in obs
    assert isinstance(obs["ticks_seen"][0], Tick)
    assert obs["checked_requests"] == 2 and obs["checked_logit_rows"] > 0
    assert obs["preemptions"] == 0
    # 3 layers of a 128-lane latent row and a 16-channel index key, float32
    assert obs["kv_latent_bytes_per_token"] == 3 * (128 + 16) * 4
    assert 0 < obs["dsa_rows_selected"] < obs["dsa_rows_scanned"]
    assert out["compared"]["index_choice_margin"][0] == 0.0
    assert set(out["end_to_end"]) == {"decode_tokens_per_s", "tpot_ms_p95"}


# what each planted fault fails first: the selection's margin where the
# fault is in the indexer, the logits everywhere
FAILS = {"indexer_left_out": "index_choice_margin",
         "selection_from_other_layer": "index_choice_margin",
         "index_rope_left_out": "index_choice_margin",
         "index_norm_left_out": "index_choice_margin",
         "index_keys_fp8": "index_choice_margin",
         "routing_unnormalised": "logit_row_gap_max"}


@pytest.mark.parametrize("fault", sorted(faults_glm.FAULTS))
def test_fault_is_not_correct(fault):
    with faults_glm.FAULTS[fault]():
        ok, out = run_tiny()
    compared = out["compared"]
    assert compared["requests_failed"][0] == 0
    assert compared["decode_compiles"][0] == compared["prefill_compiles"][0] \
        == 1
    if fault in faults_glm.INVISIBLE:
        # one positive constant on every score: no ranking moves
        assert ok and compared["index_choice_margin"][0] == 0.0
        return
    assert not ok, compared
    failing = FAILS[fault]
    assert compared[failing][0] > compared[failing][1]


@pytest.mark.parametrize("fault", [None] + [
    f for f in ref.FAULTS if f not in faults_glm.INVISIBLE])
def test_control_and_reference_faults_fail_a_limit(fault):
    """The fp8 forward pass (and the reference with each fault planted) in
    the program's place, its expert choices followed and its selections
    held to the margin: told apart from the reference."""
    sz, limits = tiny()
    w = ref.init_weights(glm_program.seed_key(SEED), sz)
    rng = np.random.default_rng(0)
    sequences = [rng.integers(0, sz["vocab"], n).tolist()
                 for n in (12, 20, 28, 36)]
    theirs = [{} for _ in sequences]
    mine = [{} for _ in sequences]
    got = ref.last_logits(w, sequences, sz, None,
                          ref.FP8 if fault is None else None, fault, theirs,
                          index=mine)
    ours = [{"chosen": r["own"][:, :len(seq)]}
            for r, seq in zip(theirs, sequences)]
    index = [{} for _ in sequences]
    want = ref.last_logits(w, sequences, sz, routing=ours, index=index)
    values = serve_hybrid.numbers(np.asarray(got), np.asarray(want),
                                  max(r["margin"] for r in ours), 0.0)
    topk = sz["kinds"][0]["index_topk"]
    values["index_choice_margin"] = max(
        ref.selection_margin(ix["scores"][layer, 0],
                             np.where(m["selected"][layer, 0] < len(s),
                                      m["selected"][layer, 0], -1), topk)
        for ix, m, s in zip(index, mine, sequences)
        for layer in range(sz["layers"]))
    assert not check.passed(serve_sparse.held(values, limits)), values


def test_reference_imports_nothing_of_the_program_and_is_expanded():
    with open(ref.__file__) as f:
        source = f.read()
    assert "import apex_tpu" not in source and "from apex_tpu" not in source
    assert 'precision=HIGHEST' in source
    assert '"sc,ncd->snd", c, uk' in source
    # the selection is lax.top_k's
    assert "jax.lax.top_k(scores" in source


# ------------------------------------------------------------ configuration


def test_configuration_equals_the_catalog_outside_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "GLM-5")
    config = harness.load_json(HERE, "configs", "glm-5.json")
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    listed = next(c for c in bench["configs"] if c["name"] == "glm-5")
    assert listed["source"] == entry["source_url"] == config["source"]
    assert sorted(listed["reduced"]) == sorted(REDUCED)
    for key, value in entry["config"].items():
        if key in REDUCED:
            assert config["source_values"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
                5, 16, 19456, 0)
    # an eighth of the vocabulary in whole lane tiles, a sixteenth of the
    # experts, one dense layer and four expert layers
    assert 19456 % 128 == 0 and 19456 >= 154880 / 8
    assert config["share"] == {"chips_per_layer": 16, "experts_first": 0,
                               "vocab_ways": 8, "layers": [0, 3, 4, 5, 6]}
    assert ref.sizes_of(config)["experts"] == (0, 1, 1, 1, 1)


def test_parameter_counts_of_the_cut():
    sz = ref.sizes_of(harness.load_json(HERE, "configs", "glm-5.json"))
    # ISSUE 37's table: 3.91 G stored, 7.82 GB in bfloat16
    assert ref.stored_params(sz) == pytest.approx(3.91e9, rel=2e-3)
    assert ref._attention_params(sz) == pytest.approx(165.0e6, rel=1e-3)
    assert ref._index_params(sz) == pytest.approx(9.4e6, rel=2e-2)
    kind = sz["kinds"][0]
    assert (kind["k_dim"], kind["v_dim"], kind["index_topk"]) == (
        256, 256, 2048)


def test_benchmark_names_the_cell_and_its_metrics():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.workload(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5", "closed-sparse-long-context", 1)
    assert [m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")
            ] == ["decode_tokens_per_s", "setup_s"]
    per_layer = [m["name"] for m in harness.metrics_of(bench, CELL,
                                                       "per_layer")]
    assert per_layer[-5:] == list(NEW)
    # the readers that count every cached row are not this cell's
    assert not any(n.startswith("latent_") for n in per_layer)
    for name in per_layer:
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))
    limits = harness.load_json(HERE, "limits", CELL + ".json")
    assert sorted(limits) == ["index_choice_margin", "logit_rms_gap",
                              "logit_row_gap_max", "router_choice_margin",
                              "served_logit_gap"]


# ----------------------------------------------------------------- traffic


def test_no_session_ends_inside_a_window_and_the_pool_holds_them():
    mix = harness.load_json(HERE, "traffic", "closed-sparse-long-context.json")
    eng = mix["engine"]
    loop = serve_hybrid.LongAnswerLoop(mix, SEED, 19456)
    first = [loop.lengths(c, 0) for c in range(mix["callers"])]
    later = [loop.lengths(c, j) for c in range(mix["callers"])
             for j in (1, 2)]
    assert all(24576 <= p <= 40960 and 8192 <= a <= 12288 for p, a in first)
    assert all(64 <= p <= 128 and 2048 <= a <= 4096 for p, a in later)
    assert max(p + a for p, a in first + later) <= eng["max_seq"]
    assert mix["callers"] == eng["max_batch"] == 16
    # the prompts and 6,000 tokens of growth a slot fit the pool
    held = sum(-(-(p + 6000) // eng["block_size"]) for p, _ in first)
    assert held <= eng["n_blocks"]
    assert mix["driver"] == "serve_sparse"


# ------------------------------------------------------------ kernel counts


def test_kernel_counts_by_hand():
    kind = {"heads": 64, "kv_rank": 512, "rope": 64, "index_heads": 32,
            "index_dim": 128}
    flops, nbytes = sparse_attention.index_scan(1000, kind)
    assert (flops, nbytes) == (1000 * 2 * 32 * 128, 1000 * 256)
    assert sparse_attention.index_select(1000) == (0, 4000)
    flops, nbytes = sparse_attention.sparse_decode(1000, kind)
    assert nbytes == 1000 * 576 * 2
    assert flops == 1000 * 2 * 64 * (576 + 512)


# ----------------------------------------------------------------- readers


INDEX_K = ('%paged_index_decode.3 = f32[16,1,53248]{2,1,0} custom-call(%q), '
           'custom_call_target="tpu_custom_call"')
SELECT = "%fusion.12 = u32[16]{0} fusion(%p), kind=kLoop"
SPARSE = "%gather.7 = bf16[16,1,2048,640]{3,2,1,0} gather(%a, %i)"


def sparse_view(ops, **observed):
    config = harness.load_json(HERE, "configs", "glm-5.json")
    device = {"busy_s": 4.0, "ops": ops, "collective_s": 0.0}
    seen = [Tick(10.06, 60.0, False, 16, []), Tick(11.06, 60.0, False, 16, [])]
    return {"trace": {"devices": [device], "busy_s": 4.0, "window_s": 5.0},
            "observed": dict(observed, ticks_seen=seen,
                             sizes=ref.sizes_of(config)),
            "chips": 1, "peaks": PEAKS}


@pytest.fixture
def scopes(monkeypatch):
    from apex_tpu.observability import spans

    table = {"paged_index_decode.3": ("paged_index_decode", INDEX_K),
             "fusion.12": ("dsa_select", SELECT),
             "gather.7": ("sparse_latent", SPARSE),
             "fusion.47": ("moe_experts", OTHER)}
    monkeypatch.setattr(spans, "program_scopes",
                        lambda: {"serving/decode": table})


def test_sparse_readers_by_hand(ring, scopes):
    plan = dict(kv_tokens=560000, kv_pages=35000, kv_tokens_latent=560000,
                dsa_rows_scanned=5 * 560000, dsa_rows_selected=5 * 32768)
    ring.extend(tick(10, 10.0, plan, {}))
    ring.extend(tick(20, 11.0, plan, {}))
    view = sparse_view({INDEX_K: [0.5, 10], SELECT: [0.25, 10],
                        SPARSE: [0.25, 10], OTHER: [3.0, 2]})
    scanned, selected = 2 * 5 * 560000, 2 * 5 * 32768
    # the scan reads 256 B a row: bytes bind
    assert read("index_scan_roofline", view) == pytest.approx(
        100 * (scanned * 256 / 819e9) / 0.5)
    assert read("index_select_roofline", view) == pytest.approx(
        100 * (scanned * 4 / 819e9) / 0.25)
    flops = selected * 2 * 64 * 1088
    assert read("sparse_decode_roofline", view) == pytest.approx(
        100 * max(flops / 197e12, selected * 1152 / 819e9) / 0.25)
    assert read("sparse_attention_time_share", view) == pytest.approx(25.0)
    assert read("index_selected_share", view) == pytest.approx(
        100 * 32768 / 560000)


def test_sparse_readers_find_nothing_in_another_program(ring, monkeypatch):
    """A program without the fields, the kernel or the scopes (the parent
    of this PR; the other cells): nothing to read, never 0."""
    from apex_tpu.observability import spans

    monkeypatch.setattr(spans, "program_scopes", lambda: {})
    ring.extend(tick(10, 10.0, dict(kv_tokens=5, kv_pages=1), {}))
    view = sparse_view({OTHER: [3.0, 2]})
    for name in NEW:
        assert read(name, view) is None, name
