"""apex_tpu.serving over a latent cache (ISSUE 33: multi-head latent
attention, softmax group-limited routing, shared experts).

A tiny preset with the published structure of the ``deepseek-v2``
configuration (``benchmark/tests/presets_deepseek/configs/
deepseek-tiny.json``: a dense layer and two expert layers, 4 heads of 8 + 8
query channels over a latent of 16 and one rotary key of 8, YaRN, 16 experts
in 4 groups of which 2 are kept, top-3, two shared experts, group 0 held)
through ``ServingEngine`` against the plain reference
``benchmark/reference/deepseek_v2.py`` (float32, the expanded form, no
cache, no kernels, no import of ``apex_tpu``): prefill-then-decode logits in
float32, the bfloat16 tolerance, the kernels against their twins,
preemption, the cache group, and the spans and counters.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import ahead_scenario                                       # noqa: E402
import flat_plan                                            # noqa: E402
from drivers import deepseek_program                       # noqa: E402
from drivers.serve_hybrid import row_gaps                  # noqa: E402
from reference import deepseek_v2 as reference             # noqa: E402

from apex_tpu import parallel                              # noqa: E402
from apex_tpu.observability import spans                   # noqa: E402
from apex_tpu.observability.metrics import MetricRegistry  # noqa: E402
from apex_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from apex_tpu.serving import model as serving_model        # noqa: E402
from apex_tpu.serving.kv_cache import (                    # noqa: E402
    CacheGroup,
    KVCacheConfig,
    init_group_arenas,
)
from apex_tpu.serving import paged_attention as pa         # noqa: E402
from apex_tpu.transformer import moe, rope                 # noqa: E402
from apex_tpu.transformer.testing import AttentionKind     # noqa: E402

PRESET = os.path.join(BENCH, "tests", "presets_deepseek", "configs",
                      "deepseek-tiny.json")
BLOCK = 4


@pytest.fixture(scope="module")
def sizes():
    with open(PRESET) as f:
        return reference.sizes_of(json.load(f))


@pytest.fixture(scope="module")
def weights(sizes):
    return reference.init_weights(deepseek_program.seed_key(5), sizes)


def build_engine(sizes, weights, dtype=jnp.float32, **serving):
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    cfg = deepseek_program.transformer_config(sizes, dtype)
    kw = dict(max_batch=4, max_seq=64, prefill_len=8, block_size=BLOCK,
              n_blocks=64, prefix_caching=False)
    kw.update(serving)
    return ServingEngine(
        cfg, ServingConfig(**kw),
        deepseek_program.program_params(weights, sizes, dtype),
        mesh=mesh, registry=MetricRegistry())


def caught_up(req):
    """Decoding, and its last token is the only one not in the cache."""
    return (not req.prefilling and req.cache_len
            == len(req.prompt) + len(req.output_tokens) - 1)


def drive(eng, lengths, seed=0, ticks=120):
    """Serve ``lengths = ((prompt, answer), ...)`` to the end; returns the
    requests and ``[(logits row, rid, sequence read)]`` of every decode
    call's caught-up slots."""
    rng = np.random.default_rng(seed)
    vocab = eng.model.cfg.padded_vocab_size
    reqs = [eng.submit(rng.integers(0, vocab, n).tolist(), m)
            for n, m in lengths]
    rows, routed, seen = [], [], None
    for _ in range(ticks):
        eng.step()
        eng.scheduler.check()
        routed.extend(eng.last_expert_choices())
        if eng.last_logits() is not None and eng.last_logits() is not seen:
            seen = eng.last_logits()
            logits, slots = seen
            logits = np.asarray(logits, np.float32)
            rows += [(logits[r.slot, 0], r.rid,
                      r.sequence_tokens()[:r.cache_len])
                     for r in eng.scheduler.running()
                     if r.slot in slots and caught_up(r)]
        if eng.scheduler.idle:
            break
    assert eng.scheduler.idle
    return reqs, rows, routed


# ------------------------------------------------- engine against reference


@pytest.mark.parametrize("fused", [True, False])
def test_prefill_then_decode_logits_match_the_reference(sizes, weights,
                                                        fused):
    """Prompts of one to four chunks that end inside, at and past block
    edges, then decoding through the latent cache (the absorbed form):
    every decode call's logits against the reference's full forward pass in
    the expanded form."""
    eng = build_engine(sizes, weights, fused_attention=fused)
    lengths = ((5, 14), (19, 9), (8, 18), (30, 7))
    reqs, rows, _ = drive(eng, lengths)
    worst = 0.0
    for got, _, seq in rows[::3]:
        want = np.asarray(reference.last_logits(weights, [seq], sizes))[0]
        worst = max(worst, float(np.abs(got - want).max()))
    assert len(rows) > 30 and worst < 2e-5, worst
    assert [len(r.output_tokens) for r in reqs] == [m for _, m in lengths]
    assert eng.decode_compile_count() == eng.prefill_compile_count() == 1
    # the arenas hold the latent row and nothing expanded: one array a
    # layer, rank + rotary channels in whole lane tiles
    (group,) = eng.cache.groups
    assert (group.latent, group.k_dim, group.v_dim, group.kv_heads) == (
        True, 16 + 8, 16, 1)
    assert all(len(layer) == 1 and layer[0].shape == (64, BLOCK, 128)
               for layer in eng.arenas[0])
    assert float(jnp.abs(eng.arenas[0][0][0][..., 24:]).max()) == 0.0


# what bfloat16 operands may cost a logit row of the tiny preset (root mean
# square against the row's own), and how far under the reference's cut a
# followed choice may lie (a difference of logits: a sound run reads row
# gaps of 0.003-0.011 and a margin of 0, router logits rounded to bfloat16
# a margin of 0.0098, fp8 operands 0.07-0.23 and 2.0).  This CPU
# runs no bfloat16 engine (its runtime has no bf16 x bf16 = f32 product at
# every shape): the reference with its
# GEMM operands rounded to bfloat16 stands in for one here, and the chip
# runs the real thing (``benchmark/tests/calibrate_deepseek.py``).
BF16_ROW_GAP, BF16_MARGIN = 0.05, 0.005


def followed(weights, sizes, seqs, **program):
    """Row gaps and margin of the reference in a program's place
    (``program``: its ``quant`` or ``fault``), its choices followed."""
    theirs = [{} for _ in seqs]
    got = reference.last_logits(weights, seqs, sizes, routing=theirs,
                                **program)
    ours = [{"chosen": r["own"][:, :len(s)]} for r, s in zip(theirs, seqs)]
    want = reference.last_logits(weights, seqs, sizes, routing=ours)
    return (row_gaps(np.asarray(got), np.asarray(want)),
            max(r["margin"] for r in ours))


@pytest.fixture(scope="module")
def sequences(sizes):
    rng = np.random.default_rng(2)
    return [rng.integers(0, sizes["vocab"], n).tolist() for n in (17, 30, 41)]


def test_bfloat16_operands_are_within_the_stated_tolerance(sizes, weights,
                                                           sequences):
    gaps, margin = followed(weights, sizes, sequences, quant=reference.BF16)
    assert 0.0 < gaps.max() < BF16_ROW_GAP, gaps
    assert margin < BF16_MARGIN, margin


def test_a_dropped_rotary_term_is_outside_the_bfloat16_tolerance(
        sizes, weights, sequences):
    gaps, _ = followed(weights, sizes, sequences, fault="rope_term_left_out")
    assert gaps.min() > BF16_ROW_GAP, gaps


def test_a_bfloat16_router_is_outside_the_bfloat16_tolerance(sizes, weights):
    """Router logits rounded to bfloat16 choose experts that are no near
    tie: the margin of the choices followed says so; float32 logits choose
    the reference's own."""
    sz = sizes
    x = jax.random.normal(jax.random.PRNGKey(3), (400, sz["hidden"]))
    router = weights["layers"][1]["router"].astype(jnp.float32)
    logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
    kw = dict(scoring="softmax", groups=(sz["n_group"], sz["topk_group"]),
              normalize=False)

    def margin(logits):
        chosen, _ = moe.route_topk(logits, None, sz["top_k"], **kw)
        return float(jnp.max(reference.route(
            x, router, sz["top_k"], sz["n_group"], sz["topk_group"],
            sz["route_scale"], chosen=chosen)[3]))

    assert margin(logits) == 0.0
    assert margin(logits.astype(jnp.bfloat16).astype(jnp.float32)) \
        > BF16_MARGIN


def test_preempted_latent_request_resumes_with_the_same_stream(sizes,
                                                               weights):
    """A request preempted out of a small pool recomputes its latent rows
    and emits the tokens it would have; the allocator stays sound."""
    def stream(n_blocks):
        eng = build_engine(sizes, weights, n_blocks=n_blocks)
        reqs, _, _ = drive(eng, ((20, 14), (18, 14), (22, 14), (17, 14)),
                           seed=3, ticks=400)
        return [r.output_tokens for r in reqs], eng.scheduler.preemptions

    roomy, none = stream(64)
    tight, some = stream(22)
    assert none == 0 and some > 0
    assert roomy == tight


# ------------------------------------------------------------- the kernels


@pytest.fixture(scope="module")
def arena():
    rng = np.random.default_rng(0)
    b, max_blocks, n_blocks = 4, 12, 64
    tables = rng.permutation(n_blocks)[:b * max_blocks].reshape(
        b, max_blocks)
    return {"rows": rng.normal(size=(n_blocks, BLOCK, 128)),
            "tables": jnp.asarray(tables, jnp.int32),
            "q": rng.normal(size=(b, 8, 4, 128))}


@pytest.mark.parametrize("spoil", [False, True], ids=["table", "spoiled"])
@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("lengths", [(0, 1, 4, 5), (16, 17, 33, 48),
                                     (3, 0, 47, 8)])
def test_decode_kernel_against_its_twin(arena, lengths, dtype, atol, spoil,
                                        monkeypatch):
    """Histories that are empty, end on a block edge, one past it, and fill
    the table; values the leading 16 lanes of the 24-channel row.  A
    ``spoil``ed table holds -1 and ids past the arena beyond each slot's
    live pages: the kernel gives the clean table's twin, and every block
    its plan names lies in the arena."""
    rows = jnp.asarray(arena["rows"], dtype)
    tables = (flat_plan.spoiled(arena["tables"], lengths, BLOCK,
                                rows.shape[0]) if spoil else arena["tables"])
    q, lengths = (jnp.asarray(arena["q"][:, 0], dtype),
                  jnp.asarray(lengths, jnp.int32))
    plans = flat_plan.plans_handed_to_the_kernel(monkeypatch)
    fused = np.asarray(pa.paged_decode_latent(
        q, rows, jnp.asarray(tables), lengths, v_dim=16, scale=0.2),
        np.float32)
    twin = np.asarray(pa.paged_decode_latent_unfused(
        q, rows, arena["tables"], lengths, v_dim=16, scale=0.2), np.float32)
    assert fused.shape == (4, 4, 16)
    np.testing.assert_allclose(fused, twin, atol=atol)
    assert not fused[np.asarray(lengths) == 0].any()
    flat_plan.assert_in_arena(plans, rows.shape[0])


def test_decode_result_does_not_depend_on_the_key_tile(arena, monkeypatch):
    args = (jnp.asarray(arena["q"][:, 0], jnp.float32),
            jnp.asarray(arena["rows"], jnp.float32), arena["tables"],
            jnp.asarray((16, 17, 33, 48), jnp.int32))
    want = np.asarray(pa.paged_decode_latent(*args, v_dim=16, scale=0.2))
    for pages in (1, 2, 5):
        monkeypatch.setattr(pa, "_MAX_LATENT_PAGES", pages)
        got = np.asarray(pa.paged_decode_latent(*args, v_dim=16, scale=0.2))
        np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("starts, chunks", [
    ((0, 0, 0, 0), (8, 3, 0, 1)), ((4, 13, 40, 5), (8, 8, 8, 2)),
    ((15, 0, 31, 9), (1, 8, 5, 7))])
def test_prefill_kernel_against_its_twin(arena, starts, chunks, dtype, atol):
    """Chunks that start at 0, on and off block edges, full and partly
    padding; each token reads the cache and the chunk up to itself."""
    limits = np.zeros((4, 8), np.int32)
    for i, (s, c) in enumerate(zip(starts, chunks)):
        limits[i, :c] = s + 1 + np.arange(c)
    lengths = jnp.asarray(np.add(starts, chunks), jnp.int32)
    args = (jnp.asarray(arena["q"], dtype), jnp.asarray(arena["rows"], dtype),
            arena["tables"], lengths, jnp.asarray(limits))
    fused = np.asarray(pa.paged_prefill_latent(*args, v_dim=16, scale=0.2),
                       np.float32)
    twin = np.asarray(pa.paged_prefill_latent_unfused(
        *args, v_dim=16, scale=0.2), np.float32)
    assert fused.shape == (4, 8, 4, 16)
    np.testing.assert_allclose(fused, twin, atol=atol)
    assert not fused[limits == 0].any()


def test_prefill_walks_query_blocks_and_key_tiles(arena, monkeypatch):
    """Two tokens a query block and two pages a key tile: the same rows."""
    limits = np.zeros((4, 8), np.int32)
    for i, (s, c) in enumerate(zip((4, 13, 40, 5), (8, 8, 8, 2))):
        limits[i, :c] = s + 1 + np.arange(c)
    args = (jnp.asarray(arena["q"], jnp.float32),
            jnp.asarray(arena["rows"], jnp.float32), arena["tables"],
            jnp.asarray(limits.max(1)), jnp.asarray(limits))
    want = np.asarray(pa.paged_prefill_latent(*args, v_dim=16, scale=0.2))
    monkeypatch.setattr(pa, "_LATENT_PREFILL_ROWS", 8)
    monkeypatch.setattr(pa, "_LATENT_PREFILL_PAGES", 2)
    got = np.asarray(pa.paged_prefill_latent(*args, v_dim=16, scale=0.2))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_latent_kernels_refuse_rows_of_another_width(arena):
    q = jnp.zeros((4, 4, 64))
    with pytest.raises(ValueError, match="latent arena"):
        pa.paged_decode_latent(q, jnp.asarray(arena["rows"]),
                               arena["tables"], jnp.zeros((4,), jnp.int32),
                               v_dim=16, scale=1.0)
    with pytest.raises(ValueError, match="values of"):
        pa.paged_decode_latent_unfused(
            jnp.zeros((4, 4, 128)), jnp.asarray(arena["rows"]),
            arena["tables"], jnp.zeros((4,), jnp.int32), v_dim=129,
            scale=1.0)


def test_a_long_prefill_call_is_walked_a_few_slots_at_a_time():
    assert serving_model._slots_a_walk(64, 128 * 128 * 640 * 2) == 8
    assert serving_model._slots_a_walk(4, 1024) == 4
    assert serving_model._slots_a_walk(6, serving_model._LATENT_WALK_BYTES
                                       // 4) == 3
    assert serving_model._slots_a_walk(5, 1 << 40) == 1


# ------------------------------------------------------------------- rotary


def test_yarn_frequencies_against_the_formula():
    """The published widths: 64 channels, base 10000, factor 40 over 4096."""
    scaling = rope.YarnScaling(factor=40.0, original_max_position=4096,
                               beta_fast=32, beta_slow=1, mscale=0.707,
                               mscale_all_dim=0.707)
    got = np.asarray(rope.yarn_inv_freq(64, 10000.0, scaling))
    dim, base = 64, 10000.0

    def correction(rotations):
        return dim * np.log(4096 / (rotations * 2 * np.pi)) / (
            2 * np.log(base))

    low, high = int(np.floor(correction(32))), int(np.ceil(correction(1)))
    assert (low, high) == (10, 23)
    for t in range(32):
        extra = base ** (-2.0 * t / dim)
        mask = 1.0 - min(max((t - low) / (high - low), 0.0), 1.0)
        want = (extra / 40.0) * (1.0 - mask) + extra * mask
        assert got[t] == pytest.approx(want, rel=1e-6)
    assert got[0] == pytest.approx(1.0) and got[31] == pytest.approx(
        base ** (-62 / 64) / 40.0, rel=1e-6)
    np.testing.assert_allclose(
        got, reference.yarn_inv_freq(64, base, {
            "factor": 40, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1}), rtol=1e-6)
    assert rope.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert rope.yarn_mscale(1.0, 0.707) == 1.0
    # equal mscale and mscale_all_dim: the tables are plain cos and sin
    cos, sin = rope.rotary_cos_sin(jnp.arange(5), 64, base, scaling=scaling)
    np.testing.assert_allclose(np.asarray(cos), np.cos(
        np.arange(5)[:, None] * got[None, :]), atol=1e-6)


def test_interleaved_rotation_is_the_pairwise_one_in_another_order():
    """Channel ``2 t`` turns with ``2 t + 1``; the result lies in half
    order, which a score of two such vectors does not see."""
    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, 6, 3, 8))
    pos = jnp.arange(6) * 7
    inv = reference.yarn_inv_freq(8, 10000.0, {
        "factor": 4, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1})
    cos, sin = rope.rotary_cos_sin(pos, 8, 10000.0, scaling=rope.YarnScaling(
        factor=4.0, original_max_position=64, mscale=0.707,
        mscale_all_dim=0.707))
    q, k, inv = jnp.asarray(q), jnp.asarray(k), jnp.asarray(inv)
    got_q = np.asarray(rope.apply_rotary_interleaved(q, cos, sin))
    got_k = np.asarray(rope.apply_rotary_interleaved(k, cos, sin))
    want_q = np.asarray(reference.rotate(q, pos, inv))
    want_k = np.asarray(reference.rotate(k, pos, inv))
    order = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    np.testing.assert_allclose(got_q, want_q[..., order], atol=1e-6)
    np.testing.assert_allclose(np.einsum("snd,snd->sn", got_q, got_k),
                               np.einsum("snd,snd->sn", want_q, want_k),
                               atol=1e-5)


# ----------------------------------------------------- description and group


def latent_kind(**changes):
    kw = dict(name="latent", num_heads=4, kv_heads=1, k_dim=16, v_dim=8,
              rotary_dim=8, latent_rank=16, q_rank=24, nope_dim=8)
    kw.update(changes)
    return AttentionKind(**kw)


def test_a_latent_kind_describes_its_cached_row():
    assert latent_kind().cache_row == (1, 24, 16, True)
    plain = AttentionKind(name="full", num_heads=4, kv_heads=2, k_dim=24,
                          v_dim=16, rotary_dim=8)
    assert plain.cache_row == (2, 24, 16, False) and not plain.latent
    for bad in (dict(kv_heads=2), dict(nope_dim=4), dict(q_rank=0),
                dict(window=8), dict(sink=True)):
        with pytest.raises(ValueError, match="latent kind"):
            latent_kind(**bad)


def test_a_latent_group_has_one_arena_a_layer_in_whole_lane_tiles():
    group = CacheGroup(layers=(0, 1, 2), kv_heads=1, k_dim=576, v_dim=512,
                       n_blocks=5, latent=True)
    assert group.row_lanes == 640
    cache = KVCacheConfig(n_layers=3, n_blocks=5, block_size=16, kv_heads=1,
                          head_dim=576, max_seq=64, dtype=jnp.bfloat16,
                          groups=(group,))
    (arenas,) = init_group_arenas(cache)
    assert [tuple(a.shape for a in layer) for layer in arenas] == [
        ((5, 16, 640),)] * 3
    assert group.first_needed_block(1000, 16) == 0      # no window


def test_a_group_that_does_not_hold_the_latent_row_is_refused(sizes):
    cfg = deepseek_program.transformer_config(sizes, jnp.float32)
    wrong = CacheGroup(layers=(0, 1, 2), kv_heads=1, k_dim=16, v_dim=8,
                       n_blocks=8)
    cache = KVCacheConfig(n_layers=3, n_blocks=8, block_size=4, kv_heads=1,
                          head_dim=16, max_seq=32, groups=(wrong,))
    with pytest.raises(ValueError, match="latent kind keeps one row"):
        serving_model.HybridDecodeModel(cfg, cache)


def test_shared_experts_and_route_scale_are_served(sizes, weights):
    """What ``unserved_fields`` refused by name before ISSUE 33."""
    cfg = deepseek_program.transformer_config(sizes, jnp.float32)
    assert cfg.hybrid.experts.shared_experts == 2
    assert cfg.hybrid.experts.route_scale == 16.0
    assert serving_model.unserved_fields(cfg.hybrid) == []
    odd = dataclasses.replace(cfg.hybrid, experts=dataclasses.replace(
        cfg.hybrid.experts, route_eps=1e-20))
    assert serving_model.unserved_fields(odd) == ["experts.route_eps"]


@pytest.mark.parametrize("left_out", ["shared", "route_scale"])
def test_a_served_field_left_out_moves_the_logits(sizes, weights, left_out):
    """The engine's logits follow ``shared_experts`` and ``route_scale``:
    the reference with either left out is another model."""
    fault = {"shared": "shared_left_out",
             "route_scale": "route_scale_left_out"}[left_out]
    seq = np.random.default_rng(5).integers(0, sizes["vocab"], 26).tolist()
    want = np.asarray(reference.last_logits(weights, [seq], sizes))
    got = np.asarray(reference.last_logits(weights, [seq], sizes,
                                           fault=fault))
    assert row_gaps(got, want)[0] > 1e-2


def test_the_trainer_refuses_a_latent_kind_by_name(sizes):
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    cfg = deepseek_program.transformer_config(sizes, jnp.float32)
    with pytest.raises(NotImplementedError) as err:
        build_gpt_3d(cfg, num_chunks=1, num_microbatches=1, mesh=mesh)
    for name in ("latent.latent_rank", "experts.groups", "experts.scoring"):
        assert name in str(err.value), name


# ------------------------------------------------------- spans and counters


def test_spans_and_counters_of_the_latent_cache_and_the_router(sizes,
                                                               weights):
    eng = build_engine(sizes, weights)
    t0 = spans.recorded()[-1].end if spans.recorded() else 0.0
    drive(eng, ((14, 8), (11, 8), (20, 8)), seed=4)
    records = [s for s in spans.recorded() if s.start >= t0]
    plans = [s for s in records if s.name == "serving/tick/decode_plan"]
    # (the tick of the first dispatch has nothing to fetch: an empty span)
    fetches = [s for s in records if s.fields and s.name in (
        "serving/tick/decode_fetch", "serving/tick/prefill_fetch")]
    last = max(plans, key=lambda s: s.fields["kv_tokens_latent"])
    assert last.fields["kv_tokens_latent"] == last.fields["kv_tokens"] > 0
    assert last.fields["kv_pages_latent"] == last.fields["kv_pages"]
    assert last.fields["kv_tokens_full"] == last.fields["kv_tokens_window"] \
        == 0
    for f in fetches:
        for field in ("moe_pairs", "moe_experts_hit", "moe_peak_pairs",
                      "moe_group_tokens"):
            assert field in f.fields
        # a token brings at most top_k pairs, and only if it kept group 0
        assert f.fields["moe_pairs"] <= 3 * f.fields["moe_group_tokens"]
    reached = eng.registry.counter("serving/moe_group_tokens").value
    assert reached == sum(f.fields["moe_group_tokens"] for f in fetches) > 0
    # two of four groups kept: about half of the (token, layer) entries
    tokens = 2 * (14 + 11 + 20 + 3 * 8 - 3)
    assert 0.2 * tokens < reached < 0.8 * tokens
    # 3 layers x 128 lanes x 4 bytes
    assert eng.registry.snapshot()["serving/kv_latent_bytes_per_token"] \
        == 3 * 128 * 4


# ---------------------------------------------------- one decode call ahead


@pytest.fixture(scope="module")
def ahead_runs(sizes, weights):
    return ahead_scenario.runs(
        build_engine(sizes, weights, **ahead_scenario.ENGINE),
        sizes["vocab"])


@pytest.mark.parametrize("i", range(len(ahead_scenario.SCRIPT)),
                         ids=ahead_scenario.KINDS)
def test_running_ahead_serves_the_settled_engines_tokens(ahead_runs, i):
    """Through the latent cache: greedy and seeded sampled requests across
    a slot turning over, a prompt chunk arriving mid-stream, an end on
    ``eos_id``, a budget and the context cap, token for token the stream of
    the same engine settled after every tick."""
    ahead_scenario.assert_same_stream(ahead_runs, i)


def test_the_drivers_contract_holds_with_a_call_in_flight(ahead_runs):
    """``ahead_scenario.Contract`` ran after every ``step()`` of both
    runs; here what the counters and the ``ahead`` field counted."""
    ahead_scenario.assert_counted(ahead_runs)
    assert ahead_runs.eng.scheduler.preemptions == 0


def test_the_ahead_engines_logits_match_the_reference(sizes, weights):
    """``drive`` pairs ``last_logits()`` with ``sequence_tokens()[:cache_len]``
    as the benchmark's driver does, one call behind the dispatch: against
    the reference in the expanded form, on an engine that ran ahead on all
    but its first call."""
    eng = build_engine(sizes, weights)
    reqs, rows, _ = drive(eng, ((9, 12), (17, 10)))
    worst = 0.0
    for got, _, seq in rows[::2]:
        want = np.asarray(reference.last_logits(weights, [seq], sizes))[0]
        worst = max(worst, float(np.abs(got - want).max()))
    assert len(rows) >= 15 and worst < 2e-5, worst
    snap = eng.registry.snapshot()
    assert snap["serving/decode_calls_ahead"] >= snap["serving/decode_calls"] - 2
    assert eng._in_flight is None
