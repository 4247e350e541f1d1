"""apex_tpu.serving over layers of more than one kind (ISSUE 27).

A tiny preset with the published structure of the ``mimo-v2-flash``
configuration (``benchmark/tests/presets_mimo/configs/mimo-tiny.json``: 7
layers ``[0,1,1,1,1,0,1]``, q/k 24 beside v 16, rotary 8, a window of 8 over
blocks of 4, 16 experts top-4 of which 4 are held, 2 and 4 KV heads) through
``ServingEngine`` against the plain reference
``benchmark/reference/mimo_v2_flash.py`` (float32, no cache, no kernels, no
import of ``apex_tpu``): prefill-then-decode logits, the share test, the
window and sink kernels, the router, the window allocator, one compile each
under churn, and the spans and counters.
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

import ahead_scenario                                      # noqa: E402
import flat_plan                                           # noqa: E402
from drivers import mimo_program                          # noqa: E402
from reference import mimo_v2_flash as reference          # noqa: E402

from apex_tpu import parallel                              # noqa: E402
from apex_tpu.observability import spans                   # noqa: E402
from apex_tpu.observability.metrics import (               # noqa: E402
    MetricRegistry,
    default_registry,
)
from apex_tpu.serving import (                             # noqa: E402
    SamplingParams,
    ServingConfig,
    ServingEngine,
)
from apex_tpu.serving import model as serving_model        # noqa: E402
from apex_tpu.serving.kv_cache import (                    # noqa: E402
    FREED,
    CacheGroup,
    KVCacheConfig,
)
from apex_tpu.serving import paged_attention as pa_module   # noqa: E402
from apex_tpu.serving.paged_attention import (             # noqa: E402
    _flat_pages_per_step,
    _step_plan,
    _vmem_bytes,
    paged_attention_decode,
    paged_attention_decode_unfused,
    paged_prefill_attention,
    paged_prefill_attention_unfused,
)
from apex_tpu.serving.scheduler import Scheduler           # noqa: E402
from apex_tpu.transformer import moe                       # noqa: E402
from apex_tpu.transformer.testing import TransformerConfig  # noqa: E402

PRESET = os.path.join(BENCH, "tests", "presets_mimo", "configs",
                      "mimo-tiny.json")
WINDOW, BLOCK = 8, 4


@pytest.fixture(scope="module")
def sizes():
    with open(PRESET) as f:
        return reference.sizes_of(json.load(f))


@pytest.fixture(scope="module")
def weights(sizes):
    return reference.init_weights(mimo_program.seed_key(5), sizes)


def build_engine(sizes, weights, **serving):
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    cfg = mimo_program.transformer_config(sizes, jnp.float32)
    kw = dict(max_batch=4, max_seq=64, prefill_len=8, block_size=BLOCK,
              n_blocks=64, prefix_caching=False)
    kw.update(serving)
    return ServingEngine(
        cfg, ServingConfig(**kw),
        mimo_program.program_params(weights, sizes, jnp.float32),
        mesh=mesh, registry=MetricRegistry())


def caught_up(req):
    """Decoding, and its last token is the only one not in the cache."""
    return (not req.prefilling and req.cache_len
            == len(req.prompt) + len(req.output_tokens) - 1)


# ------------------------------------------------- engine against reference


@pytest.mark.parametrize("fused", [True, False])
def test_prefill_then_decode_logits_match_the_reference(sizes, weights,
                                                        fused):
    """Prompts below, at and past the window and of one to four chunks,
    then decoding through both cache groups: every decode call's logits
    against the reference's full forward pass."""
    eng = build_engine(sizes, weights, fused_attention=fused)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, sizes["vocab"], n).tolist(), m)
            for n, m in ((5, 20), (19, 12), (8, 30), (3, 6), (30, 10))]
    worst, compared = 0.0, 0
    for _ in range(80):
        eng.step()
        eng.scheduler.check()
        if eng.last_logits() is not None:
            logits, slots = eng.last_logits()
            logits = np.asarray(logits)
            for r in eng.scheduler.running():
                if r.slot in slots and caught_up(r):
                    seq = r.sequence_tokens()[:r.cache_len]
                    want = np.asarray(
                        reference.last_logits(weights, [seq], sizes))[0]
                    worst = max(worst, float(
                        np.abs(logits[r.slot, 0] - want).max()))
                    compared += 1
        if eng.scheduler.idle:
            break
    assert eng.scheduler.idle and compared > 40
    assert worst < 2e-5, worst
    assert [len(r.output_tokens) for r in reqs] == [20, 12, 30, 6, 10]
    assert eng.decode_compile_count() == eng.prefill_compile_count() == 1
    assert eng.scheduler.window_blocks_freed > 0


def test_expert_choices_of_every_call_are_the_reference_own(sizes, weights):
    """``last_expert_choices()`` names, call by call, the rows that were
    tokens and their positions; put together they are what the reference's
    routers choose at every position of a sequence (fp32: no near tie)."""
    from drivers.serve_hybrid import choices_by_request

    eng = build_engine(sizes, weights)
    assert eng.last_expert_choices() == []
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, sizes["vocab"], n).tolist(), m)
            for n, m in ((19, 6), (5, 9), (11, 4))]
    routed = []
    while not eng.scheduler.idle:
        eng.step()
        calls = eng.last_expert_choices()
        # a tick's prefill and decode dispatches; the last tick only fetches
        assert len(calls) <= 2 and (calls or eng.scheduler.idle)
        for chosen, rows in calls:
            assert chosen.shape[0] == sum(sizes["experts"])
            assert chosen.shape[2] == sizes["top_k"]
            assert all(first + n <= chosen.shape[1]
                       for _, first, _, n in rows)
        routed += calls
    for req in reqs:
        seq = req.sequence_tokens()[:-1]
        got = choices_by_request(routed, {req.rid: len(seq)})[req.rid]
        assert (got >= 0).all()
        own = {}
        reference.last_logits(weights, [seq], sizes, routing=[own])
        np.testing.assert_array_equal(np.sort(got, -1),
                                      np.sort(own["own"][:, :len(seq)], -1))


def test_greedy_tokens_are_the_reference_argmax(sizes, weights):
    eng = build_engine(sizes, weights)
    prompt = np.random.default_rng(1).integers(0, sizes["vocab"], 13)
    req = eng.submit(prompt.tolist(), 12)
    eng.run_until_drained()
    gaps = reference.served_token_gaps(weights, prompt, req.output_tokens,
                                       sizes)
    assert float(np.max(gaps)) < 1e-4


def test_one_compile_each_under_churn(sizes, weights):
    """Requests joining, finishing and preempting (a full pool) and a
    sampled caller move values only."""
    eng = build_engine(sizes, weights, n_blocks=20)
    rng = np.random.default_rng(2)
    pending = [(int(rng.integers(3, 30)), int(rng.integers(4, 24)))
               for _ in range(14)]
    done = []
    for tick in range(400):
        if pending and tick % 2 == 0:
            n, m = pending.pop()
            sampling = (SamplingParams(temperature=0.8, top_k=5, seed=tick)
                        if tick % 6 == 0 else None)
            done.append(eng.submit(
                rng.integers(0, sizes["vocab"], n).tolist(), m,
                sampling=sampling))
        preempted, in_flight = eng.scheduler.preemptions, eng._in_flight
        eng.step()
        eng.scheduler.check()
        if eng.scheduler.preemptions > preempted and in_flight is not None:
            # no request is preempted with a row in flight: the tick settled
            # before it planned, and its dispatch ran on a current view
            dispatch = [s for s in spans.recorded()
                        if s.name == "serving/tick/decode_dispatch"][-1]
            assert dispatch.fields["ahead"] == 0
        if not pending and eng.scheduler.idle:
            break
    assert eng.scheduler.idle
    assert all(len(r.output_tokens) == r.max_new_tokens for r in done)
    assert eng.scheduler.preemptions > 0
    assert eng.decode_compile_count() == eng.prefill_compile_count() == 1
    snap = eng.registry.snapshot()
    assert snap["serving/decode_rows_discarded"] == 0
    assert 0 < snap["serving/decode_calls_ahead"] < snap["serving/decode_calls"]


def test_preempted_request_resumes_with_the_same_stream(sizes, weights):
    """A request preempted out of a small pool recomputes through both
    cache groups and emits the tokens it would have."""
    def stream(n_blocks):
        eng = build_engine(sizes, weights, n_blocks=n_blocks)
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.integers(0, sizes["vocab"], n).tolist(), 20)
                for n in (20, 18, 22, 17)]
        for _ in range(400):
            eng.step()
            eng.scheduler.check()
            if eng.scheduler.idle:
                break
        return [r.output_tokens for r in reqs], eng.scheduler.preemptions

    roomy, none = stream(64)
    tight, some = stream(24)
    assert none == 0 and some > 0
    assert roomy == tight


def test_a_call_in_flight_reads_the_tables_it_was_planned_with(sizes,
                                                               weights):
    """The engine rewrites its block tables in place as it plans the next
    tick (a preempted slot's row among them) while the call it dispatched
    may not have run: every decode call here waits behind a slow
    computation on the arenas, so the host always plans first.  On the CPU
    ``jnp.asarray`` of a numpy array aliases it, and the call read the next
    plan's tables (the stream above then differed under load, ISSUE 37);
    the engine hands each call a copy."""
    @jax.jit
    def slow(arenas):
        x = jnp.ones((512, 512))
        for _ in range(12):
            x = jnp.tanh(x @ x / 512.0)
        return jax.tree_util.tree_map(
            lambda a: a + 0 * x[0, 0].astype(a.dtype), arenas)

    def stream(n_blocks, hold):
        eng = build_engine(sizes, weights, n_blocks=n_blocks)
        if hold:
            real = eng._decode
            eng._decode = lambda arenas, *rest: real(slow(arenas), *rest)
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.integers(0, sizes["vocab"], n).tolist(), 20)
                for n in (20, 18, 22, 17)]
        for _ in range(400):
            eng.step()
            eng.scheduler.check()
            if eng.scheduler.idle:
                break
        return [r.output_tokens for r in reqs], eng.scheduler.preemptions

    roomy, _ = stream(64, False)
    held, some = stream(24, True)
    assert some > 0
    assert roomy == held


# ------------------------------------------------------------ the share test


def test_the_four_shares_add_up_to_the_uncut_layer(sizes):
    """Each share routes over all 16 experts and adds its own 4; the four
    partial results add up to what the uncut reference gives for the whole
    layer, and each program share equals the reference's share."""
    sz = dict(sizes, held=(0, 16))
    key = jax.random.PRNGKey(7)
    layer = next(i for i, e in enumerate(sz["experts"]) if e)
    shapes = reference.layer_shapes(sz, layer)
    lw = {name: mean + std * jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32)
        for i, (name, (shape, std, mean)) in enumerate(
            sorted(shapes.items()))}
    x = jax.random.normal(jax.random.fold_in(key, 99), (40, sz["hidden"]))
    whole = np.asarray(reference.expert_layer(x, lw, sz))
    total = np.zeros_like(whole)
    for first in (0, 4, 8, 12):
        share = dict(lw, experts_gate_up=lw["experts_gate_up"][first:first + 4],
                     experts_down=lw["experts_down"][first:first + 4])
        want = np.asarray(reference.expert_layer(x, share, sz,
                                                 held=(first, 4)))
        got, pairs, chosen, _ = moe.held_experts_ffn(
            x, share["router"], share["router_bias"],
            share["experts_gate_up"], share["experts_down"],
            top_k=sz["top_k"], held=(first, 4))
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
        assert int(pairs.sum()) > 0
        # every share routes over all the experts: the same choices, and
        # its pairs are those of them that fall on its own experts
        np.testing.assert_array_equal(
            np.asarray(chosen),
            np.asarray(reference.route(x, lw["router"], lw["router_bias"],
                                       sz["top_k"])[0]))
        assert int(pairs.sum()) == int(np.sum(
            (np.asarray(chosen) >= first) & (np.asarray(chosen) < first + 4)))
        total += np.asarray(got)
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert float(np.abs(whole).max()) > 1e-3


# ------------------------------------------------------------------ router


def test_router_bias_selects_and_does_not_weigh():
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0]])
    no_bias = jnp.zeros((6,))
    experts, weights = moe.route_topk(logits, no_bias, 2)
    assert experts.tolist() == [[0, 1]]
    scores = jax.nn.sigmoid(logits[0])
    np.testing.assert_allclose(
        weights[0], scores[:2] / scores[:2].sum(), rtol=1e-6)
    # a bias lifts expert 4 into the choice; its weight is its own score
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 1.0, 0.0])
    experts, weights = moe.route_topk(logits, bias, 2)
    assert sorted(experts[0].tolist()) == [0, 4]
    picked = scores[experts[0]]
    np.testing.assert_allclose(weights[0], picked / picked.sum(), rtol=1e-6)
    np.testing.assert_allclose(float(weights.sum()), 1.0, rtol=1e-6)


def test_router_ties_go_to_the_lower_expert_like_the_reference():
    logits = jnp.zeros((3, 8))
    bias = jnp.zeros((8,))
    experts, weights = moe.route_topk(logits, bias, 3)
    assert experts.tolist() == [[0, 1, 2]] * 3
    np.testing.assert_allclose(weights, 1.0 / 3.0, rtol=1e-6)
    ref_experts, ref_weights, own, margin = reference.route(
        jnp.zeros((3, 4)), jnp.zeros((4, 8)), bias, 3)
    assert np.asarray(ref_experts).tolist() == experts.tolist()
    assert np.asarray(own).tolist() == experts.tolist()
    assert np.asarray(margin).tolist() == [0.0] * 3
    np.testing.assert_allclose(ref_weights, weights, rtol=1e-6)


def test_padding_rows_are_routed_nowhere():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (12, 16))
    router = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    gate_up = jax.random.normal(jax.random.fold_in(key, 2), (8, 16, 8))
    down = jax.random.normal(jax.random.fold_in(key, 3), (8, 4, 16))
    live = jnp.arange(12) < 5
    y, pairs, _, _ = moe.held_experts_ffn(x, router, jnp.zeros((8,)), gate_up,
                                       down, top_k=2, held=(0, 8), live=live)
    assert int(pairs.sum()) == 5 * 2
    assert float(jnp.abs(y[5:]).max()) == 0.0
    full, _, _, _ = moe.held_experts_ffn(x, router, jnp.zeros((8,)), gate_up,
                                      down, top_k=2, held=(0, 8))
    np.testing.assert_allclose(y[:5], full[:5], atol=1e-6)


# ----------------------------------------------------- window, sink kernels


def dense_attention(q, k_arena, v_arena, table, length, g, window, sinks):
    """One slot's decode attention, plainly."""
    n, dk = q.shape
    nb, bs, _ = k_arena.shape
    keys = np.concatenate([k_arena[b] for b in table]).reshape(-1, g, dk)
    vals = np.concatenate([v_arena[b] for b in table]).reshape(
        len(keys), g, -1)
    out = np.zeros((n, vals.shape[-1]))
    if length == 0:
        return out
    lo = 0 if window is None else max(length - window, 0)
    for h in range(n):
        kv = h // (n // g)
        a = q[h] @ keys[lo:length, kv].T / np.sqrt(dk)
        m = a.max() if sinks is None else max(a.max(), sinks[h])
        e = np.exp(a - m)
        denom = e.sum() + (0.0 if sinks is None else np.exp(sinks[h] - m))
        out[h] = (e / denom) @ vals[lo:length, kv]
    return out


@pytest.fixture(scope="module")
def arena():
    rng = np.random.default_rng(0)
    b, n, g, dk, dv, nb, mb = 6, 8, 2, 24, 16, 48, 8
    return dict(
        b=b, n=n, g=g, dk=dk, dv=dv, mb=mb,
        k=rng.normal(size=(nb, BLOCK, g * dk)).astype(np.float32),
        v=rng.normal(size=(nb, BLOCK, g * dv)).astype(np.float32),
        tables=rng.permutation(nb)[:b * mb].reshape(b, mb).astype(np.int32),
        sinks=rng.normal(size=(n,)).astype(np.float32), rng=rng)


@pytest.mark.parametrize("spoil", [False, True], ids=["table", "spoiled"])
@pytest.mark.parametrize("window, sink", [(None, False), (WINDOW, False),
                                          (WINDOW, True), (None, True)])
@pytest.mark.parametrize("lengths", [
    [0, 1, 3, 4, 5, 7],          # below the window, round a block edge
    [8, 9, 7, 12, 13, 16],       # at the window, and just past it
    [17, 20, 21, 31, 32, 29],    # well past it, the table's end
])
def test_decode_kernel_window_and_sinks(arena, window, sink, lengths, spoil,
                                        monkeypatch):
    """A ``spoil``ed table holds -1 and ids past the arena outside each
    slot's live pages (past the length, behind the window): the kernel
    reads the same rows, and every block its plan names lies in the
    arena (ISSUE 36)."""
    a = arena
    q = a["rng"].normal(size=(a["b"], a["n"], a["dk"])).astype(np.float32)
    sinks = a["sinks"] if sink else None
    kw = dict(kv_heads=a["g"], window=window,
              sinks=None if sinks is None else jnp.asarray(sinks))
    n_blocks = a["k"].shape[0]
    tables = (flat_plan.spoiled(a["tables"], lengths, BLOCK, n_blocks, window)
              if spoil else a["tables"])
    args = (jnp.asarray(q), jnp.asarray(a["k"]), jnp.asarray(a["v"]),
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))
    plans = flat_plan.plans_handed_to_the_kernel(monkeypatch)
    fused = np.asarray(paged_attention_decode(*args, **kw))
    flat_plan.assert_in_arena(plans, n_blocks)
    unfused = np.asarray(paged_attention_decode_unfused(
        *args[:3], jnp.asarray(a["tables"]), args[4], **kw))
    want = np.stack([dense_attention(q[i], a["k"], a["v"], a["tables"][i],
                                     lengths[i], a["g"], window, sinks)
                     for i in range(a["b"])])
    np.testing.assert_allclose(fused, want, atol=2e-6)
    np.testing.assert_allclose(unfused, want, atol=2e-6)
    assert fused.shape == (a["b"], a["n"], a["dv"])


def test_a_window_one_token_too_wide_is_told_apart(arena):
    a = arena
    q = a["rng"].normal(size=(a["b"], a["n"], a["dk"])).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(a["k"]), jnp.asarray(a["v"]),
            jnp.asarray(a["tables"]),
            jnp.asarray([12, 20, 9, 30, 8, 17], jnp.int32))
    right = paged_attention_decode(*args, kv_heads=a["g"], window=WINDOW)
    wide = paged_attention_decode(*args, kv_heads=a["g"], window=WINDOW + 1)
    diff = np.abs(np.asarray(right) - np.asarray(wide)).max(axis=(1, 2))
    assert (diff[[0, 1, 2, 3, 5]] > 1e-3).all()
    assert diff[4] < 1e-6        # a history of 8 lies inside both


@pytest.mark.parametrize("window, sink", [(None, False), (WINDOW, False),
                                          (WINDOW, True)])
@pytest.mark.parametrize("starts, chunks", [
    ([0, 3, 10, 21, 26, 0], [6, 6, 4, 6, 2, 0]),
    ([0, 0, 7, 8, 20, 15], [1, 6, 6, 6, 6, 5]),
])
def test_prefill_kernel_window_and_sinks(arena, window, sink, starts, chunks):
    """Chunks that start below, at and past the window: each token against
    the decode kernel's own reference at its horizon."""
    a = arena
    T = 6
    limits = np.zeros((a["b"], T), np.int32)
    lengths = np.zeros((a["b"],), np.int32)
    for i, (lo, c) in enumerate(zip(starts, chunks)):
        limits[i, :c] = np.arange(lo + 1, lo + c + 1)
        lengths[i] = lo + c if c else 0
    q = a["rng"].normal(size=(a["b"], T, a["n"], a["dk"])).astype(np.float32)
    sinks = a["sinks"] if sink else None
    kw = dict(kv_heads=a["g"], window=window,
              sinks=None if sinks is None else jnp.asarray(sinks))
    args = (jnp.asarray(q), jnp.asarray(a["k"]), jnp.asarray(a["v"]),
            jnp.asarray(a["tables"]), jnp.asarray(lengths),
            jnp.asarray(limits))
    fused = np.asarray(paged_prefill_attention(*args, **kw))
    unfused = np.asarray(paged_prefill_attention_unfused(*args, **kw))
    for i in range(a["b"]):
        for t in range(T):
            want = dense_attention(q[i, t], a["k"], a["v"], a["tables"][i],
                                   limits[i, t], a["g"], window, sinks)
            np.testing.assert_allclose(fused[i, t], want, atol=2e-6)
            if limits[i, t]:
                np.testing.assert_allclose(unfused[i, t], want, atol=2e-6)


def test_step_plan_drops_the_pages_behind_the_window():
    tables = jnp.arange(4 * 16, dtype=jnp.int32).reshape(4, 16)
    lengths = jnp.asarray([0, 5, 33, 64], jnp.int32)
    first = jnp.maximum(lengths - WINDOW, 0) // BLOCK
    n_all, *_ = _step_plan(tables, lengths, BLOCK, 2)
    n_win, plan, slot, group = _step_plan(tables, lengths, BLOCK, 2, first)
    # whole histories: 1 + 1 + 5 + 8 steps; windows: at most 3 pages a slot
    assert int(n_all) == 15 and int(n_win) == 1 + 1 + 2 + 1
    plan = np.asarray(plan).reshape(-1, 2)[:int(n_win)]
    assert plan[2].tolist() == [2 * 16 + 6, 2 * 16 + 7]     # pages 6, 7
    assert plan[3, 0] == 2 * 16 + 8                          # page 8
    assert plan[4].tolist() == [3 * 16 + 14, 3 * 16 + 15]
    # and without a window the plan is the one it always was
    same = _step_plan(tables, lengths, BLOCK, 2, jnp.zeros((4,), jnp.int32))
    for x, y in zip(_step_plan(tables, lengths, BLOCK, 2), same):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# the decode kernels at the widths that brought them (ISSUE 27), bfloat16,
# blocks of 16: a grid step's pages are one key tile (ISSUE 32)
CELL_KINDS = {"full": dict(g=4, window=None, sink=False),
              "window": dict(g=8, window=128, sink=True)}
CELL_N, CELL_DK, CELL_DV, CELL_BLOCK, CELL_TABLE = 64, 192, 128, 16, 104


def cell_pages(kind, dtype=jnp.bfloat16, max_blocks=CELL_TABLE):
    """The pages of a key tile that the module derives at the cell's
    widths."""
    c = CELL_KINDS[kind]
    page = max(_vmem_bytes((CELL_BLOCK, c["g"] * CELL_DK), dtype),
               _vmem_bytes((CELL_BLOCK, c["g"] * CELL_DV), dtype))
    return _flat_pages_per_step(page, max_blocks, CELL_BLOCK, c["window"])


def cell_case(kind, lengths, dtype, seed=0):
    """Arenas, a table of distinct blocks and queries at the cell's widths;
    the values are the dtype's own, so a float64 reference over them differs
    by the kernel's rounding alone."""
    c = CELL_KINDS[kind]
    rng = np.random.default_rng(seed)
    b, nb = len(lengths), len(lengths) * CELL_TABLE

    def draw(*shape):
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        return x.astype(dtype)

    return dict(
        args=(draw(b, CELL_N, CELL_DK), draw(nb, CELL_BLOCK, c["g"] * CELL_DK),
              draw(nb, CELL_BLOCK, c["g"] * CELL_DV),
              jnp.asarray(rng.permutation(nb).reshape(b, CELL_TABLE),
                          jnp.int32),
              jnp.asarray(lengths, jnp.int32)),
        kw=dict(kv_heads=c["g"], window=c["window"],
                sinks=(jnp.asarray(4.0 + rng.normal(size=(CELL_N,)),
                                   jnp.float32) if c["sink"] else None)))


def cell_lengths(kind, dtype=jnp.bfloat16):
    """0, 1, a row short of a page, exactly a step, a row past its edge and
    a history of several steps (the table's end)."""
    step = cell_pages(kind, dtype) * CELL_BLOCK
    return [0, 1, CELL_BLOCK - 1, step, step + 1, CELL_TABLE * CELL_BLOCK]


@pytest.mark.parametrize("kind", ["full", "window"])
def test_decode_kernel_at_the_cells_widths(kind):
    lengths = cell_lengths(kind)
    case = cell_case(kind, lengths, jnp.bfloat16)
    fused = np.asarray(paged_attention_decode(
        *case["args"], **case["kw"]).astype(jnp.float32))
    unfused = np.asarray(paged_attention_decode_unfused(
        *case["args"], **case["kw"]).astype(jnp.float32))
    q, k, v, tables, _ = (np.asarray(x.astype(jnp.float32))
                          if x.dtype == jnp.bfloat16 else np.asarray(x)
                          for x in case["args"])
    sinks = case["kw"]["sinks"]
    want = np.stack([dense_attention(
        q[i], k, v, tables[i], lengths[i], case["kw"]["kv_heads"],
        case["kw"]["window"], None if sinks is None else np.asarray(sinks))
        for i in range(len(lengths))])
    assert fused.shape == (len(lengths), CELL_N, CELL_DV)
    assert not fused[0].any()                   # length 0: the zero row
    # probabilities rounded to bfloat16 for ``p v``, a bfloat16 result
    np.testing.assert_allclose(fused, want, atol=2e-2)
    np.testing.assert_allclose(fused, unfused, atol=2e-2)


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pages", [1, 2, 8])
@pytest.mark.parametrize("kind", ["full", "window"])
def test_decode_result_does_not_depend_on_the_key_tile(kind, pages, dtype,
                                                       atol, monkeypatch):
    """The same inputs through key tiles of 1, 2 and 8 pages and through the
    derived one: float32 arenas (the ``HIGHEST`` path) to rounding, bfloat16
    to its tolerance."""
    case = cell_case(kind, cell_lengths(kind, dtype)[1:], dtype, seed=1)
    derived = np.asarray(paged_attention_decode(
        *case["args"], **case["kw"]).astype(jnp.float32))
    monkeypatch.setattr(pa_module, "_flat_pages_per_step", lambda *a: pages)
    forced = np.asarray(paged_attention_decode(
        *case["args"], **case["kw"]).astype(jnp.float32))
    np.testing.assert_allclose(forced, derived, atol=atol)


@pytest.mark.parametrize("lengths", [
    [129, 130, 143, 144, 145, 160],       # just past the window
    [1000, 2047, 2048, 2049, 4097, 6144],  # long histories, the table's end
    [0, 1, 127, 128, 500, 6143],          # and with slots below it
])
def test_a_window_slots_sweep_is_one_grid_step(lengths):
    """At window 128 over blocks of 16 the derived key tile holds the 9 pages
    a window can touch, so the plan has one step a slot."""
    pages = cell_pages("window", max_blocks=384)
    assert pages == 9
    lengths = jnp.asarray(lengths, jnp.int32)
    b = lengths.shape[0]
    tables = jnp.arange(b * 384, dtype=jnp.int32).reshape(b, 384)
    first = jnp.maximum(lengths - 128, 0) // CELL_BLOCK
    n_steps, plan, slot, group = _step_plan(tables, lengths, CELL_BLOCK,
                                            pages, first)
    assert int(n_steps) == b
    assert np.asarray(slot)[:b].tolist() == list(range(b))
    assert not np.asarray(group)[:b].any()
    # and every row a query can read lies in its step's tile
    last = -(-np.asarray(lengths) // CELL_BLOCK)
    assert ((last - np.asarray(first)) <= pages).all()


@pytest.mark.parametrize("kind", ["full", "window"])
def test_key_tile_gauges_read_what_the_plan_says(kind):
    case = cell_case(kind, [40, 300], jnp.bfloat16)
    reg = default_registry()
    for name in ("keys_per_step", "steps_per_slot_max", "copies_per_step"):
        reg.gauge(f"paged_decode/{name}/{kind}").set(-1)
    jax.jit(lambda *xs: paged_attention_decode(*xs, **case["kw"])).lower(
        *case["args"])                          # traced, not run
    snap = reg.snapshot()
    pages = cell_pages(kind)
    assert snap[f"paged_decode/keys_per_step/{kind}"] == pages * CELL_BLOCK
    span = CELL_TABLE if kind == "full" else 9
    assert snap[f"paged_decode/steps_per_slot_max/{kind}"] == -(-span // pages)
    # a K and a V page a live page: 64 at the cell's widths, 18 a window
    assert snap[f"paged_decode/copies_per_step/{kind}"] == 2 * pages
    assert 2 * pages == {"full": 64, "window": 18}[kind]


@pytest.mark.parametrize("kind", ["full", "window", "latent"])
def test_the_plan_indexes_the_arena_whatever_the_table_holds(kind,
                                                            monkeypatch):
    """Table entries anywhere in [-3, 2 x n_blocks), live pages included:
    the plan the kernel copies by is clamped into the arena, so a copy
    issued without Mosaic's bounds checks never leaves it (ISSUE 36)."""
    rng = np.random.default_rng(7)
    b, n, g, d, nb, mb = 4, 8, 2, 128, 20, 8
    tables = jnp.asarray(rng.integers(-3, 2 * nb, (b, mb)), jnp.int32)
    lengths = jnp.asarray([0, 5, 17, mb * BLOCK], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(nb, BLOCK, g * d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, n, d)), jnp.float32)
    plans = flat_plan.plans_handed_to_the_kernel(monkeypatch)
    if kind == "latent":
        out = pa_module.paged_decode_latent(
            jnp.concatenate([q, q], -1), rows, tables, lengths, v_dim=d,
            scale=0.1)
    else:
        out = paged_attention_decode(
            q, rows, rows, tables, lengths, kv_heads=g,
            window=None if kind == "full" else WINDOW)
    assert np.isfinite(np.asarray(out)).all()
    flat_plan.assert_in_arena(plans, nb)


def test_a_pooled_arena_takes_no_window(arena):
    a = arena
    k4 = jnp.asarray(a["k"]).reshape(-1, BLOCK, a["g"], a["dk"])
    with pytest.raises(NotImplementedError):
        paged_attention_decode(
            jnp.zeros((a["b"], a["n"], a["dk"])), k4, k4,
            jnp.asarray(a["tables"]), jnp.zeros((a["b"],), jnp.int32),
            window=WINDOW)


# ------------------------------------------------------ the window allocator


def window_cache(n_blocks=32, window_blocks=12, max_seq=64):
    groups = (CacheGroup(layers=(0,), kv_heads=2, k_dim=8, v_dim=8,
                         n_blocks=n_blocks),
              CacheGroup(layers=(1, 2), kv_heads=4, k_dim=8, v_dim=8,
                         n_blocks=window_blocks, window=WINDOW))
    return KVCacheConfig(n_layers=1, n_blocks=n_blocks, block_size=BLOCK,
                         kv_heads=2, head_dim=8, max_seq=max_seq,
                         groups=groups)


def test_window_group_frees_only_what_no_query_can_read():
    sched = Scheduler(window_cache(), 2, chunk_tokens=8,
                      prefix_caching=False)
    req = sched.submit(list(range(30)), 8)
    assert sched.admit() == [req]
    assert len(req.blocks) == len(req.more_blocks[0]) == 2
    sched.check()
    req.cache_len = 8
    # the next query sits at position 8 and reads keys 1..8: block 0 stays
    assert sched.free_behind_window(req, 8) == 0
    sched.try_grow_to(req, 16)
    req.cache_len = 16
    # position 16 reads 9..16: blocks 0 and 1 (keys 0..7) go, block 2 stays
    assert sched.free_behind_window(req, 16) == 2
    assert req.more_blocks[0][:3] == [FREED, FREED, req.more_blocks[0][2]]
    assert FREED not in req.blocks           # the full group keeps all
    sched.check()
    assert sched.window_blocks_held() == 2 and sched.window_blocks_freed == 2
    # a block a query can still read is never handed back, whatever is asked
    req.cache_len = 17
    assert sched.free_behind_window(req, 17) == 0
    sched.finish(req)
    sched.check()
    assert all(a.n_free == a.n_blocks for a in sched.allocators)


def test_check_catches_a_block_freed_too_early():
    sched = Scheduler(window_cache(), 2, chunk_tokens=8,
                      prefix_caching=False)
    req = sched.submit(list(range(20)), 4)
    sched.admit()
    sched.try_grow_to(req, 16)
    req.cache_len = 16
    sched.free_behind_window(req, 20)        # one block too many
    with pytest.raises(AssertionError, match="still reads"):
        sched.check()


def test_admission_and_preemption_count_both_groups():
    # the window pool holds one request's chunk and window, not two
    sched = Scheduler(window_cache(window_blocks=5), 2, chunk_tokens=8,
                      prefix_caching=False)
    first = sched.submit(list(range(20)), 4)
    second = sched.submit(list(range(20)), 4)
    assert sched.admit() == [first, second]
    assert sched.allocators[1].n_free == 1
    # growing the older request takes the newer one's blocks of both groups
    assert sched.try_grow_to(first, 16) == 16
    assert second.state.value == "waiting" and sched.preemptions == 1
    assert second.blocks == [] and second.more_blocks == []
    sched.check()
    assert sched.kv_occupancy() == pytest.approx(4 / 5)


def test_prefix_sharing_is_refused_with_a_window_group():
    with pytest.raises(ValueError, match="prefix caching"):
        Scheduler(window_cache(), 2, chunk_tokens=8)
    with pytest.raises(ValueError, match="occupancy"):
        Scheduler(window_cache(), 2, chunk_tokens=8, prefix_caching=False,
                  admission="reserve")


def test_a_window_pool_too_small_for_one_request_is_refused():
    sched = Scheduler(window_cache(window_blocks=3), 2, chunk_tokens=8,
                      prefix_caching=False)
    with pytest.raises(ValueError, match="blocks"):
        sched.submit(list(range(20)), 4)


def test_engine_refuses_what_hybrid_layers_do_not_take(sizes, weights):
    with pytest.raises(ValueError, match="prefix caching"):
        build_engine(sizes, weights, prefix_caching=True)
    eng = build_engine(sizes, weights)
    req = eng.submit([1, 2, 3], 4)
    eng.step()
    eng.step()
    with pytest.raises(NotImplementedError):
        eng.export_request(req)


# ------------------------------------------------- one group stays one group


def test_a_model_of_identical_layers_is_served_as_before():
    """No hybrid description: the uniform walker, the pooled 5-D arena, one
    table, no window bookkeeping."""
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    cfg = TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=4,
        padded_vocab_size=64, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0)
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=2, num_microbatches=1,
                                 mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0), jnp.zeros((2, 2), jnp.int32))
    eng = ServingEngine(cfg, ServingConfig(max_batch=2, max_seq=32), params,
                        mesh=mesh, registry=MetricRegistry())
    assert type(eng.model) is serving_model.DecodeModel
    assert eng.cache.groups == () and len(eng.cache.cache_groups) == 1
    assert len(eng.arenas) == 2 and eng.arenas[0].ndim == 5
    assert eng._group_tables[0] is eng._tables and not eng._windowed
    req = eng.submit([3, 1, 4, 1, 5], 6)
    eng.run_until_drained()
    eng.scheduler.check()
    assert len(req.output_tokens) == 6
    plan = [s for s in spans.recorded()
            if s.name == "serving/tick/decode_plan"][-1]
    assert "kv_tokens" in plan.fields and "kv_tokens_window" not in plan.fields


def test_serving_config_still_refuses_the_switch_layer():
    with pytest.raises(NotImplementedError, match="Switch"):
        serving_model.serving_config(TransformerConfig(num_experts=4))


# ------------------------------------------------------- spans and counters


def test_spans_and_counters_of_both_caches_and_the_router(sizes, weights):
    eng = build_engine(sizes, weights)
    rng = np.random.default_rng(4)
    for n in (14, 11, 20):
        eng.submit(rng.integers(0, sizes["vocab"], n).tolist(), 16)
    t0 = spans.recorded()[-1].end if spans.recorded() else 0.0
    eng.run_until_drained()
    records = [s for s in spans.recorded() if s.start >= t0]
    plans = [s for s in records if s.name == "serving/tick/decode_plan"]
    fetches = [s for s in records if s.name == "serving/tick/decode_fetch"]
    # the tick of the first dispatch has nothing to fetch yet: an empty span
    assert not fetches[0].fields
    fetches = [s for s in fetches if s.fields]
    assert fetches and len(plans) >= len(fetches)  # a tick may only prefill
    last = max(plans, key=lambda s: s.fields["kv_tokens_full"])
    for field in ("kv_tokens_full", "kv_tokens_window", "kv_pages_full",
                  "kv_pages_window", "window_blocks_held",
                  "window_blocks_freed"):
        assert field in last.fields
    # per layer of the kind: a full layer reads the histories, a window
    # layer at most 8 rows of each, in at most 3 blocks
    slots = 3                        # three callers decoded together
    assert last.fields["kv_tokens_full"] == last.fields["kv_tokens"]
    assert last.fields["kv_pages_full"] == last.fields["kv_pages"]
    assert last.fields["kv_tokens_window"] <= slots * WINDOW
    assert last.fields["kv_tokens_window"] < last.fields["kv_tokens_full"]
    assert last.fields["kv_pages_window"] <= slots * 3
    n_layers, held = sum(sizes["experts"]), sizes["held"][1]
    for f in fetches:
        assert 0 <= f.fields["moe_experts_hit"] <= n_layers * held
        assert f.fields["moe_peak_pairs"] <= f.fields["moe_pairs"]
    pairs = eng.registry.counter("serving/moe_pairs").value
    prefill = [s for s in records if s.name == "serving/tick/prefill_fetch"]
    assert pairs == sum(s.fields["moe_pairs"] for s in fetches + prefill)
    assert pairs > 0
    assert eng.registry.counter("serving/window_blocks_freed").value \
        == sum(s.fields["window_blocks_freed"] for s in plans)


# ---------------------------------------------------- one decode call ahead


@pytest.fixture(scope="module")
def ahead_runs(sizes, weights):
    return ahead_scenario.runs(
        build_engine(sizes, weights, **ahead_scenario.ENGINE),
        sizes["vocab"])


@pytest.mark.parametrize("i", range(len(ahead_scenario.SCRIPT)),
                         ids=ahead_scenario.KINDS)
def test_running_ahead_serves_the_settled_engines_tokens(ahead_runs, i):
    """Greedy and seeded sampled requests across a slot turning over, a
    prompt chunk arriving mid-stream, window blocks handed back, an end on
    ``eos_id``, a budget and the context cap: token for token the stream of
    the same engine settled after every tick."""
    ahead_scenario.assert_same_stream(ahead_runs, i)


def test_the_drivers_contract_holds_with_a_call_in_flight(ahead_runs):
    """``ahead_scenario.Contract`` ran after every ``step()`` of both
    runs; here what the counters and the ``ahead`` field counted.  The
    window group handed blocks back all the while (``check()`` held with a
    call in flight after every tick)."""
    ahead_scenario.assert_counted(ahead_runs)
    assert ahead_runs.eng.scheduler.window_blocks_freed > 0
    assert ahead_runs.eng.scheduler.preemptions == 0


def test_last_logits_are_the_delivered_calls_and_a_drain_settles(
        ahead_runs, sizes):
    """On the scenario's engine, idle again (the last to use it: a drain
    is for good)."""
    eng = ahead_runs.eng
    calls = eng.registry.snapshot()["serving/decode_calls"]
    before = eng.last_logits()
    prompt = np.random.default_rng(6).integers(0, sizes["vocab"], 7)
    req = eng.submit(prompt.tolist(), 6)
    eng.step()                  # the prompt and the first decode dispatch
    assert eng._in_flight is not None and eng.last_logits() is before
    assert len(req.output_tokens) == 1 and req.cache_len == 7
    eng.step()                  # dispatches the second, delivers the first
    logits, slots = eng.last_logits()
    assert slots == (req.slot,) and len(req.output_tokens) == 2
    assert eng._in_flight.logits is not logits
    assert int(np.argmax(np.asarray(logits)[req.slot, 0])) \
        == req.output_tokens[-1]
    # the host's counts are the delivered ones; the call in flight writes
    # the position after them
    assert req.cache_len == 8
    (chosen, rows), = eng.last_expert_choices()
    assert rows == ((req.rid, req.slot, 8, 1),)
    eng.settle()
    assert eng._in_flight is None and len(req.output_tokens) == 3
    assert eng.last_logits()[0] is not logits
    eng.settle()                # nothing in flight: nothing happens
    assert len(req.output_tokens) == 3
    eng.run_until_drained()
    assert eng._in_flight is None and len(req.output_tokens) == 6
    assert eng.registry.snapshot()["serving/decode_calls"] == calls + 5
    # a drain delivers what is in flight before it cancels the queue
    first = eng.submit([1, 2, 3], 8)
    for _ in range(3):
        eng.step()
    waiting = eng.submit([4, 5, 6, 7], 8)
    seen = len(first.output_tokens)
    assert eng._in_flight is not None
    assert eng.drain() == [waiting]
    assert eng._in_flight is None and len(first.output_tokens) == seen + 1
    eng.run_until_drained()
    assert len(first.output_tokens) == 8 and eng._in_flight is None
