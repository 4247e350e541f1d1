"""apex_tpu.serving with learned sparse attention over a latent cache (ISSUE
37: GLM-5's indexer, an exact top-k select, the sparse absorbed attention;
sigmoid routing with a selection bias, normalised weights and one shared
expert).

A tiny preset with the published structure of the ``glm-5`` configuration
(``benchmark/tests/presets_glm/configs/glm-tiny.json``: a dense layer and
two expert layers, 4 heads of 8 + 8 query channels over a latent of 16 and
one rotary key of 8, an indexer of 4 heads of 16 channels that keeps the
top 8 tokens, 16 experts top-3 with one shared, 4 held) through
``ServingEngine`` against the plain reference ``benchmark/reference/
glm_5.py`` (float32, the expanded form, no cache, no kernels): logits and
each query's selection, the kernels against their twins, the select against
``lax.top_k``, preemption through both arenas, the experts' shares, and the
spans, counters and scopes.
"""

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

from drivers import glm_program                            # noqa: E402
from drivers.serve_hybrid import choices_by_request        # noqa: E402
from reference import glm_5 as reference                   # noqa: E402

from apex_tpu import parallel                              # noqa: E402
from apex_tpu.observability import spans                   # noqa: E402
from apex_tpu.observability.metrics import MetricRegistry  # noqa: E402
from apex_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from apex_tpu.serving import sparse_attention as sa        # noqa: E402
from apex_tpu.serving.kv_cache import (                    # noqa: E402
    CacheGroup,
    KVCacheConfig,
    init_group_arenas,
)
from apex_tpu.serving import paged_attention as pa         # noqa: E402
from apex_tpu.transformer import moe                       # noqa: E402
from apex_tpu.transformer.testing import AttentionKind     # noqa: E402

PRESET = os.path.join(BENCH, "tests", "presets_glm", "configs",
                      "glm-tiny.json")
BLOCK = 4
TOPK = 8


@pytest.fixture(scope="module")
def sizes():
    with open(PRESET) as f:
        return reference.sizes_of(json.load(f))


@pytest.fixture(scope="module")
def weights(sizes):
    return reference.init_weights(glm_program.seed_key(5), sizes)


def build_engine(sizes, weights, dtype=jnp.float32, **serving):
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    cfg = glm_program.transformer_config(sizes, dtype)
    kw = dict(max_batch=4, max_seq=64, prefill_len=8, block_size=BLOCK,
              n_blocks=64, prefix_caching=False)
    kw.update(serving)
    return ServingEngine(
        cfg, ServingConfig(**kw),
        glm_program.program_params(weights, sizes, dtype),
        mesh=mesh, registry=MetricRegistry())


def caught_up(req):
    return (not req.prefilling and req.cache_len
            == len(req.prompt) + len(req.output_tokens) - 1)


def drive(eng, lengths, seed=0, ticks=200):
    """Serve ``lengths`` to the end; returns the requests, ``[(logits row,
    selections [layers, k], rid, sequence read)]`` of every decode call's
    caught-up slots, and the calls' expert choices."""
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
            picked = np.asarray(eng.last_index_choices())
            rows += [(logits[r.slot, 0], picked[:, r.slot], r.rid,
                      r.sequence_tokens()[:r.cache_len])
                     for r in eng.scheduler.running()
                     if r.slot in slots and caught_up(r)]
        if eng.scheduler.idle:
            break
    assert eng.scheduler.idle
    return reqs, rows, routed


# ------------------------------------------------- engine against reference


@pytest.mark.parametrize("fused", [True, False])
def test_prefill_then_decode_matches_the_reference(sizes, weights, fused):
    """Prompts of one to four chunks, then decoding through the latent and
    index caches: every third decode row's logits and every sparse layer's
    selection against the reference's full forward pass (the expanded
    form), the program's expert choices followed (f32 near ties)."""
    eng = build_engine(sizes, weights, fused_attention=fused)
    lengths = ((5, 14), (19, 9), (8, 18), (30, 7))
    reqs, rows, routed = drive(eng, lengths)
    wanted = {}
    for _, _, rid, seq in rows:
        wanted[rid] = max(wanted.get(rid, 0), len(seq))
    chosen = choices_by_request(routed, wanted)
    worst, checked_past_k = 0.0, 0
    for got, picked, rid, seq in rows[::3]:
        index = {}
        want = np.asarray(reference.last_logits(
            weights, [seq], sizes,
            routing=[{"chosen": chosen[rid][:, :len(seq)]}],
            index=[index]))[0]
        worst = max(worst, float(np.abs(got - want).max()))
        for layer in range(len(picked)):
            mine = sorted(int(p) for p in picked[layer] if p >= 0)
            theirs = sorted(int(p) for p in index["selected"][layer, 0]
                            if p < len(seq))
            assert mine == theirs, (layer, len(seq))
            assert len(mine) == min(TOPK, len(seq))
        checked_past_k += len(seq) > TOPK
    # f32 end to end: the same arithmetic in another order
    assert worst < 2e-5, worst
    assert checked_past_k > 5
    assert [len(r.output_tokens) for r in reqs] == [m for _, m in lengths]
    assert eng.decode_compile_count() == eng.prefill_compile_count() == 1


def test_the_cache_group_keeps_index_keys_beside_the_latent_rows(sizes,
                                                                 weights):
    eng = build_engine(sizes, weights)
    (group,) = eng.cache.groups
    assert (group.latent, group.k_dim, group.index_dim) == (True, 24, 16)
    # per layer the latent rows in whole lane tiles and the index keys,
    # both under the one table
    assert all(len(layer) == 2 and layer[0].shape == (64, BLOCK, 128)
               and layer[1].shape == (64, BLOCK, 16)
               for layer in eng.arenas[0])
    assert eng.registry.snapshot()["serving/kv_latent_bytes_per_token"] \
        == 3 * (128 + 16) * 4


def test_preempted_sparse_request_resumes_with_the_same_stream(sizes,
                                                               weights):
    """A request preempted out of a small pool recomputes its latent rows
    and its index keys and emits the tokens it would have."""
    def stream(n_blocks):
        eng = build_engine(sizes, weights, n_blocks=n_blocks)
        reqs, _, _ = drive(eng, ((20, 14), (18, 14), (22, 14), (17, 14)),
                           seed=3, ticks=400)
        return [r.output_tokens for r in reqs], eng.scheduler.preemptions

    roomy, none = stream(64)
    tight, some = stream(22)
    assert none == 0 and some > 0
    assert roomy == tight


def test_spans_counters_and_scopes_of_the_sparse_layers(sizes, weights):
    """The ``decode_plan`` span carries the rows the indexers scanned and
    the queries selected, the counters add them up (prefill too), and the
    three new scopes are named in the compiled programs."""
    eng = build_engine(sizes, weights)
    before = len(spans.recorded())
    drive(eng, ((12, 6), (3, 6)))
    plans = [s for s in spans.recorded()[before:]
             if s.name == "serving/tick/decode_plan"
             and "dsa_rows_scanned" in s.fields]
    assert plans
    layers = sizes["layers"]
    for s in plans:
        assert s.fields["dsa_rows_scanned"] % layers == 0
        assert s.fields["dsa_rows_selected"] <= s.fields["dsa_rows_scanned"]
    snap = eng.registry.snapshot()
    # prompt 12 (positions 0-11) and 3: each scans its history, each
    # selects at most TOPK, in every layer
    scanned = layers * (sum(range(1, 13)) + sum(range(1, 4)))
    selected = layers * (sum(min(p, TOPK) for p in range(1, 13)) + 6)
    assert snap["dsa/prefill_rows_scanned"] == scanned
    assert snap["dsa/prefill_rows_selected"] == selected
    assert snap["dsa/rows_scanned"] == sum(
        s.fields["dsa_rows_scanned"] for s in plans)
    scopes = {scope for table in spans.program_scopes().values()
              for scope, _ in table.values()}
    assert {"dsa_index", "dsa_select", "sparse_latent"} <= scopes


# ---------------------------------------------------------------- kernels


@pytest.fixture(scope="module")
def index_arena():
    rng = np.random.default_rng(0)
    b, max_blocks, n_blocks, d = 4, 12, 64, 16
    tables = rng.permutation(n_blocks)[:b * max_blocks].reshape(b,
                                                                max_blocks)
    return (jnp.asarray(rng.normal(size=(n_blocks, BLOCK, d)), jnp.float32),
            jnp.asarray(tables, jnp.int32))


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 2e-3)])
@pytest.mark.parametrize("lengths", [(0, 1, 5, 8), (9, 16, 17, 33),
                                     (40, 48, 47, 24)],
                         ids=["under", "about", "over"])
@pytest.mark.parametrize("queries", [1, 3])
def test_index_kernel_against_its_twin(index_arena, lengths, dtype, atol,
                                       queries):
    """Histories under, at and past TOPK, one query a slot (decode) and a
    chunk (prefill, each query with its own horizon)."""
    arena, tables = index_arena
    rng = np.random.default_rng(sum(lengths) + queries)
    q = jnp.asarray(rng.normal(size=(4, queries, 4, 16)), dtype)
    w = jnp.asarray(rng.normal(size=(4, queries, 4)), jnp.float32)
    limits = np.maximum(np.asarray(lengths)[:, None]
                        - np.arange(queries)[::-1][None], 0)
    got = sa.paged_index_scores(q, w, arena.astype(dtype), tables,
                                jnp.asarray(limits))
    want = sa.paged_index_scores_unfused(q, w, arena.astype(dtype), tables,
                                         jnp.asarray(limits))
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (4, queries, 48)
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
    assert np.isinf(got[np.arange(48)[None, None] >= limits[..., None]]).all()
    live = np.isfinite(want)
    np.testing.assert_allclose(got[live], want[live], atol=atol, rtol=atol)


def test_index_kernel_tiles_a_table_in_lane_tiles():
    assert sa._index_pages(32, 16, 3328) == 128
    assert sa._index_pages(128 * 32, 16, 3328) == 16
    assert sa._index_pages(32, 4, 12) == 12


# jitted, so that cases of one shape share a trace
SELECT = jax.jit(sa.select_topk, static_argnums=1)
SELECT_MASK = jax.jit(sa.select_topk_mask, static_argnums=1)
SELECT_CASES = ["random", "ties", "padded", "short", "all_ties",
                "mixed_sign", "neg_inf_row", "rows_13", "aligned",
                "ties_at_cut"]


def _select_case(case):
    """``(scores [rows, L] float32, k)`` of a case: 6 rows of 300 (not a
    whole number of 128-lane chunks) unless the case says otherwise."""
    rng = np.random.default_rng(SELECT_CASES.index(case))
    rows, n, k = 6, 300, 8
    if case == "rows_13":
        rows = 13
    if case == "aligned":
        n = 384
    x = rng.normal(size=(rows, n)).astype(np.float32)
    limits = np.full(rows, n)
    if case == "ties":
        x = np.round(x)
    if case == "padded":
        limits = rng.integers(0, n, rows)
    if case == "short":
        limits = np.asarray([0, 1, 3, 7, 8, 9])
    if case == "all_ties":
        x[:] = 1.5
    if case == "mixed_sign":
        # negative rows, mixed rows, and zeros of both signs at the cut
        x[0] = -np.abs(x[0])
        x[1] = np.round(x[1]) * 0.0
        x[2, ::7] = -0.0
        x[2, 3::7] = 0.0
        x[2, :5] = 1.0
    if case == "neg_inf_row":
        limits[[1, 4]] = 0
    if case == "ties_at_cut":
        # 5 above the cut, 11 ties at it spread over the row, room for 3
        x = np.minimum(x, 0.5)
        x[:, rng.choice(n, 16, replace=False)] = 2.0
        for r in range(rows):
            x[r, rng.choice(np.flatnonzero(x[r] == 2.0), 5,
                            replace=False)] = 3.0
    x = np.where(np.arange(n)[None] < limits[:, None], x, -np.inf)
    return x.astype(np.float32), k


@pytest.mark.parametrize("case", SELECT_CASES)
def test_select_is_lax_top_k_as_a_set_in_position_order(case):
    x, k = _select_case(case)
    rows = x.shape[0]
    pos, live = SELECT(jnp.asarray(x), k)
    pos, live = np.asarray(pos), np.asarray(live)
    _, idx = jax.lax.top_k(jnp.asarray(x), k)
    for r in range(rows):
        want = sorted(int(i) for i in np.asarray(idx)[r]
                      if np.isfinite(x[r, i]))
        assert list(pos[r][live[r]]) == want
        assert list(pos[r]) == sorted(pos[r])
    if case == "all_ties":
        assert (pos == np.arange(k)[None]).all()
    if case == "neg_inf_row":
        assert (pos[1] == np.arange(k)).all() and not live[1].any()
    if case == "ties_at_cut":
        assert (live.sum(axis=1) == k).all()


@pytest.mark.parametrize("case", SELECT_CASES)
def test_select_mask_is_the_set_of_live_positions(case):
    """``select_topk_mask`` is 1 exactly at ``select_topk``'s live
    positions, int8, leading dimensions kept."""
    x, k = _select_case(case)
    x = jnp.asarray(x).reshape((1,) + x.shape)
    pos, live = SELECT(x, k)
    mask = np.asarray(SELECT_MASK(x, k))
    assert mask.dtype == np.int8 and mask.shape == x.shape
    assert pos.shape == live.shape == (1, x.shape[1], k)
    want = np.zeros(x.shape, np.int8)
    for r in range(x.shape[1]):
        want[0, r, np.asarray(pos)[0, r][np.asarray(live)[0, r]]] = 1
    np.testing.assert_array_equal(mask, want)


def test_select_counts_positions_without_a_gather():
    """The prefix count turns the set into positions with sums and 0/1
    products: an XLA gather of 2,048 scalars a row was most of the
    select's time on the TPU, ahead of its 32 passes."""
    text = jax.jit(sa.select_topk, static_argnums=1).lower(
        jax.ShapeDtypeStruct((16, 53248), jnp.float32), 2048).as_text()
    assert "gather" not in text


def test_select_of_fewer_columns_than_k_keeps_them_all():
    x = jnp.asarray([[1.0, -jnp.inf, 3.0], [2.0, 2.0, 2.0]])
    pos, live = sa.select_topk(x, 5)
    assert np.asarray(pos).tolist() == [[0, 1, 2, 3, 4]] * 2
    assert np.asarray(live).tolist() == [[True, False, True, False, False],
                                         [True, True, True, False, False]]


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 2e-2)])
def test_sparse_attention_of_every_row_is_the_dense_kernels(dtype, atol):
    """Selecting every row of each history is dense latent attention: the
    gathered rows against the page-visiting kernel's twin."""
    rng = np.random.default_rng(1)
    b, max_blocks, n_blocks, d, dv = 3, 6, 24, 128, 16
    arena = jnp.asarray(rng.normal(size=(n_blocks, BLOCK, d)), dtype)
    tables = jnp.asarray(rng.permutation(n_blocks)[:b * max_blocks]
                         .reshape(b, max_blocks), jnp.int32)
    lengths = jnp.asarray([1, 13, 24], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 4, d)), dtype)
    pos = jnp.broadcast_to(jnp.arange(24), (b, 24))
    live = pos < lengths[:, None]
    got = sa.sparse_latent_attention(q, sa.gather_rows(arena, tables, pos),
                                     live, v_dim=dv, scale=0.3)
    want = pa.paged_decode_latent_unfused(q, arena, tables, lengths,
                                          v_dim=dv, scale=0.3)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_gathered_rows_are_rows_not_pages():
    arena = jnp.arange(8 * BLOCK * 2, dtype=jnp.float32).reshape(8, BLOCK, 2)
    tables = jnp.asarray([[5, 2, 7]], jnp.int32)
    rows = sa.gather_rows(arena, tables, jnp.asarray([[0, 5, 11]]))
    want = [arena[5, 0], arena[2, 1], arena[7, 3]]
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(want))


# ------------------------------------------------------------- the model


def sparse_kind(**changes):
    kw = dict(name="s", num_heads=4, kv_heads=1, k_dim=16, v_dim=8,
              rotary_dim=8, latent_rank=16, q_rank=24, nope_dim=8,
              index_heads=4, index_dim=16, index_rope_dim=8, index_topk=8)
    kw.update(changes)
    return AttentionKind(**kw)


def test_a_sparse_kind_describes_its_index_row():
    kind = sparse_kind()
    assert kind.sparse and kind.index_row == 16
    assert kind.cache_row == (1, 24, 16, True)
    assert not sparse_kind(index_topk=0).sparse
    assert sparse_kind(index_topk=0).index_row == 0
    for bad in (dict(index_heads=0), dict(index_rope_dim=5),
                dict(index_rope_dim=32)):
        with pytest.raises(ValueError):
            sparse_kind(**bad)
    with pytest.raises(ValueError):
        AttentionKind(name="x", num_heads=4, kv_heads=4, k_dim=8, v_dim=8,
                      rotary_dim=8, index_heads=4, index_dim=16,
                      index_rope_dim=8, index_topk=8)


def test_a_sparse_group_has_two_arenas_a_layer():
    group = CacheGroup(layers=(0, 1), kv_heads=1, k_dim=24, v_dim=16,
                       n_blocks=10, latent=True, index_dim=16)
    cfg = KVCacheConfig(n_layers=2, n_blocks=10, block_size=BLOCK,
                        kv_heads=1, head_dim=24, max_seq=32,
                        dtype=jnp.bfloat16, groups=(group,))
    arenas = init_group_arenas(cfg)
    assert [tuple(a.shape for a in layer) for layer in arenas[0]] == [
        ((10, BLOCK, 128), (10, BLOCK, 16))] * 2


def test_the_index_weights_scale_is_the_sources():
    assert sa.index_weights_scale(32, 128) == pytest.approx(
        32 ** -0.5 * 128 ** -0.5)


# -------------------------------------------------------- the share test


def test_the_four_shares_add_up_to_the_uncut_layer(sizes):
    """Sigmoid scores, a selection bias, normalised weights times 2.5 and
    one shared expert: each share routes over all 16 experts and adds its
    own 4; the four partial results, the shared expert counted once, add
    up to what the uncut reference gives for the whole layer, and each
    program share equals the reference's share."""
    sz = dict(sizes, held=(0, 16))
    key = jax.random.PRNGKey(7)
    layer = next(i for i, e in enumerate(sz["experts"]) if e)
    lw = {name: mean + std * jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32)
        for i, (name, (shape, std, mean)) in enumerate(
            sorted(reference.layer_shapes(sz, layer).items()))}
    x = jax.random.normal(jax.random.fold_in(key, 99), (40, sz["hidden"]))
    whole = np.asarray(reference.expert_layer(x, lw, sz))
    total = np.zeros_like(whole)
    for first in (0, 4, 8, 12):
        share = dict(lw,
                     experts_gate_up=lw["experts_gate_up"][first:first + 4],
                     experts_down=lw["experts_down"][first:first + 4])
        want = np.asarray(reference.expert_layer(
            x, share, sz, held=(first, 4), shared=first == 0))
        got, pairs, _, _ = moe.held_experts_ffn(
            x, share["router"], share["router_bias"],
            share["experts_gate_up"], share["experts_down"],
            top_k=sz["top_k"], held=(first, 4), route_scale=2.5,
            shared=((share["shared_gate_up"], share["shared_down"])
                    if first == 0 else None))
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
        assert int(pairs.sum()) > 0
        total += np.asarray(got)
    np.testing.assert_allclose(total, whole, atol=1e-5)


# ------------------------------------------------------------ the reference


def test_the_references_column_buckets_change_nothing(sizes, weights,
                                                      monkeypatch):
    """The reference reads a query block's keys up to its bucket's end:
    the same logits and selections as one bucket."""
    seq = np.random.default_rng(4).integers(0, sizes["vocab"], 700)
    one = {}
    want = np.asarray(reference.last_logits(weights, [seq], sizes,
                                            index=[one]))
    monkeypatch.setattr(reference, "COL_BUCKET", 256)
    split = {}
    got = np.asarray(reference.last_logits(weights, [seq], sizes,
                                           index=[split]))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.sort(split["selected"], -1),
                                  np.sort(one["selected"], -1))


def test_the_selection_margin_by_hand():
    scores = np.asarray([5.0, 1.0, 4.0, 3.0, 2.0, -np.inf])
    spread = float(np.std(scores[:5]))
    assert reference.selection_margin(scores, [0, 2, 3], 3) == 0.0
    # the fourth best for the third: one score apart, both sides
    assert reference.selection_margin(scores, [0, 2, 4], 3) == \
        pytest.approx(1.0 / spread)
    assert reference.selection_margin(scores, [0, 2, 5], 3) == np.inf
    # every live position selected, or not
    assert reference.selection_margin(scores[:3], [0, 1, 2], 3) == 0.0
    assert reference.selection_margin(scores[:3], [0, 1], 3) == np.inf
