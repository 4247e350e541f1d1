"""The trainer of layers of more than one kind (``build_gpt_3d`` with a
``TransformerConfig.hybrid``) and the dropless expert layer's backward pass,
at a tiny size on the CPU with seeded weights, against the benchmark's plain
reference of Trinity-Mini (``benchmark/reference/trinity_mini.py``, float32,
no import of ``apex_tpu``): the expert layer's gradients, no pair dropped
under any skew, the shares adding up to the uncut layer, the trainer's loss,
every leaf's gradient and three Adam steps, the step's operation count by
hand, and the named refusals.
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
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from drivers import trinity_program                         # noqa: E402
from kernels import (                                       # noqa: E402
    hybrid_attention, hybrid_model_flops, moe_train)
from reference import trinity_mini as reference             # noqa: E402

from apex_tpu import parallel                                # noqa: E402
from apex_tpu.observability import MetricRegistry, TrainStatsLogger  # noqa: E402
from apex_tpu.optimizers import FusedAdam                    # noqa: E402
from apex_tpu.transformer import moe                         # noqa: E402
from apex_tpu.transformer.testing import HybridParams        # noqa: E402
from apex_tpu.transformer.testing.gpt_parallel_train import (  # noqa: E402
    build_gpt_3d)

PRESET = os.path.join(ROOT, "benchmark", "tests", "presets_trinity",
                      "configs", "trinity-tiny.json")


@pytest.fixture(scope="module")
def sizes():
    with open(PRESET) as f:
        return reference.sizes_of(json.load(f))


@pytest.fixture
def mesh():
    yield parallel.initialize_model_parallel(devices=jax.devices()[:1])
    parallel.destroy_model_parallel()


# ------------------------------------------------------- the expert layer


def expert_weights(key, T=96, h=32, f=16, E=16, count=4):
    ks = jax.random.split(key, 7)
    return dict(
        x=jax.random.normal(ks[0], (T, h)),
        router=0.5 * jax.random.normal(ks[1], (h, E)),
        bias=0.1 * jax.random.normal(ks[2], (E,)),
        gate_up=0.2 * jax.random.normal(ks[3], (count, h, 2 * f)),
        down=0.2 * jax.random.normal(ks[4], (count, f, h)),
        shared=(0.2 * jax.random.normal(ks[5], (h, 2 * f)),
                0.2 * jax.random.normal(ks[6], (f, h))))


def dense_loop(x, router, bias, gate_up, down, shared, top_k, held, eps,
               scale):
    """The same layer as a loop over the held experts, each over every
    token, in plain ``jax.numpy``."""
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    _, experts = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, experts, 1)
    weights = picked / (picked.sum(1, keepdims=True) + eps) * scale
    y = moe.swiglu(x, *shared)
    for e in range(gate_up.shape[0]):
        w = jnp.sum(jnp.where(experts == held[0] + e, weights, 0.0), 1)
        y = y + w[:, None] * moe.swiglu(x, gate_up[e], down[e])
    return y


@pytest.mark.parametrize("skew", ["as_drawn", "every_token_on_one_expert",
                                  "two_passes"])
def test_held_experts_gradients_match_a_dense_loop(skew):
    """Output and the gradient of every input that has one, with the
    routing as drawn, with every token choosing one held expert (no pair may
    be dropped), and with so many pairs here that a second pass runs."""
    T = 1024 if skew == "two_passes" else 96
    w = expert_weights(jax.random.PRNGKey(0), T=T)
    held, top_k = (4, 4), 4
    if skew == "every_token_on_one_expert":
        w["bias"] = w["bias"].at[5].set(100.0)
    if skew == "two_passes":
        # every token chooses all four held experts: 4096 pairs where a
        # pass takes 1.5 * 4096 / 4 = 1536
        w["bias"] = w["bias"].at[4:8].set(100.0)
        assert moe._chunk_rows(T * top_k, 4 / 16) == 1536
    cot = jax.random.normal(jax.random.PRNGKey(1), w["x"].shape)
    kw = dict(top_k=top_k, held=held)

    def program(x, router, gate_up, down, shared):
        return moe.held_experts_ffn(
            x, router, w["bias"], gate_up, down, route_eps=1e-20,
            route_scale=2.5, shared=shared, **kw)

    def plain(x, router, gate_up, down, shared):
        return dense_loop(x, router, w["bias"], gate_up, down, shared,
                          top_k, held, 1e-20, 2.5)

    args = (w["x"], w["router"], w["gate_up"], w["down"], w["shared"])
    y, pairs, experts, _ = program(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(plain(*args)),
                               rtol=1e-5, atol=1e-5)
    local = np.asarray(experts) - held[0]
    np.testing.assert_array_equal(
        np.asarray(pairs), np.bincount(local[(local >= 0) & (local < 4)],
                                       minlength=4))
    if skew == "every_token_on_one_expert":
        assert int(pairs[1]) == T
    if skew == "two_passes":
        assert int(pairs.sum()) == 4 * T
    got = jax.jit(jax.grad(lambda *a: jnp.sum(program(*a)[0] * cot),
                           argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


def test_grouped_matmul_gradients():
    """The rows' and the weights' gradient against one matmul a group; a
    group no row fell on gets zeros."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    lhs = jax.random.normal(ks[0], (40, 16))
    rhs = jax.random.normal(ks[1], (4, 16, 24))
    cot = jax.random.normal(ks[2], (40, 24))
    sizes = jnp.asarray([10, 0, 22, 8], jnp.int32)
    group = np.repeat(np.arange(4), np.asarray(sizes))

    def plain(lhs, rhs):
        return jnp.einsum("mk,mkn->mn", lhs, rhs[group],
                          precision="highest")

    got = jax.grad(lambda a, b: jnp.sum(moe.grouped_matmul(a, b, sizes)
                                        * cot), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda a, b: jnp.sum(plain(a, b) * cot),
                    argnums=(0, 1))(lhs, rhs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
    assert np.all(np.asarray(got[1][1]) == 0.0)


def test_route_topk_scale_and_epsilon_and_what_the_gradient_follows():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0])
    experts, weights = moe.route_topk(logits, bias, 2)
    plain = np.asarray(jax.nn.sigmoid(logits))[0]
    assert sorted(np.asarray(experts)[0].tolist()) == [0, 2]
    np.testing.assert_allclose(float(weights.sum()), 1.0, rtol=1e-6)
    _, scaled = moe.route_topk(logits, bias, 2, 1e-20, 2.5)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(weights),
                               rtol=1e-6)
    # the weights' gradient reaches the chosen scores and no other; the
    # bias, which only chooses, gets none
    g_logits, g_bias = jax.grad(
        lambda lg, b: moe.route_topk(lg, b, 2)[1][0, 0], argnums=(0, 1))(
            logits, bias)
    assert np.all(np.asarray(g_bias) == 0.0)
    assert np.all(np.asarray(g_logits)[0, [1, 3]] == 0.0)
    assert np.all(np.asarray(g_logits)[0, [0, 2]] != 0.0)
    assert plain[0] > plain[2]


def test_the_shares_add_up_to_the_uncut_layer(sizes):
    """The four shares' routed parts plus the shared expert counted once are
    the uncut reference's whole layer."""
    sz = sizes
    whole = dict(sz, held=(0, sz["n_experts"]))
    lw = reference.init_weights(jax.random.PRNGKey(3), whole)["layers"][2]
    m = jax.random.normal(jax.random.PRNGKey(4), (48, sz["hidden"]))
    want, _, _ = reference.expert_layer(m, lw, whole)
    dtype = jnp.float32
    total = moe.swiglu(m, lw["shared_gate_up"], lw["shared_down"])
    count = sz["held"][1]
    for first in range(0, sz["n_experts"], count):
        part, pairs, _, _ = moe.held_experts_ffn(
            m, lw["router"], lw["router_bias"],
            lw["experts_gate_up"][first:first + count].astype(dtype),
            lw["experts_down"][first:first + count].astype(dtype),
            top_k=sz["top_k"], held=(first, count), route_eps=1e-20,
            route_scale=sz["route_scale"])
        total = total + part
        share, _, _ = reference.expert_layer(
            m, dict(lw, experts_gate_up=lw["experts_gate_up"][
                first:first + count], experts_down=lw["experts_down"][
                    first:first + count]), sz, held=(first, count),
            shared=False)
        np.testing.assert_allclose(np.asarray(part), np.asarray(share),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_every_seed_draws_its_own_weights_and_batches(sizes):
    """``--seed`` makes the weights and every step's batch: two seeds share
    neither, one seed gives the same twice, and the QK-norm's gains lie
    round the configuration's ``qk_norm_mean`` where the others lie round
    1."""
    from drivers import train_hybrid

    sz = sizes
    mean = dict(sz["init"])["qk_norm_mean"]
    a, again, b = (reference.init_weights(jax.random.PRNGKey(seed), sz)
                   for seed in (1, 1, 2))
    for name in ("wq", "router", "router_bias", "experts_down"):
        np.testing.assert_array_equal(a["layers"][2][name],
                                      again["layers"][2][name])
        assert not np.allclose(a["layers"][2][name], b["layers"][2][name])
    assert float(jnp.mean(a["layers"][2]["q_norm"])) == pytest.approx(
        mean, abs=0.1)
    assert float(jnp.mean(a["layers"][2]["k_norm"])) == pytest.approx(
        mean, abs=0.1)
    assert float(jnp.mean(a["layers"][2]["norm1"])) == pytest.approx(
        1.0, abs=0.05)
    traffic = {"batch": 2, "seq": 64}
    first = train_hybrid.batch_of(1, 0, traffic, sz["vocab"])
    np.testing.assert_array_equal(
        first, train_hybrid.batch_of(1, 0, traffic, sz["vocab"]))
    assert first.shape == (2, 64) and first.max() < sz["vocab"]
    for other in (train_hybrid.batch_of(1, 1, traffic, sz["vocab"]),
                  train_hybrid.batch_of(2, 0, traffic, sz["vocab"])):
        assert (first != other).mean() > 0.9


@pytest.mark.parametrize("qk_norm_mean, low, high",
                         [(1.0, 0.5, 1.0), (2.0, 0.0, 0.2)])
def test_what_the_qk_norm_gains_do_to_attentions_output(qk_norm_mean, low,
                                                        high):
    """Why the seeded QK-norm gains lie round 2: with gains round 1 a
    random head averages hundreds of keys, the post-norm scales the
    average back up, and most of the second layer's attention output is
    one direction that every position shares (which a router turns into
    favourite experts); at 2 a head reads a few keys and its output is its
    own position's."""
    with open(PRESET) as f:
        config = json.load(f)
    window, seq = 512, 1024
    config["sliding_window"] = window
    config["assumed"]["init"]["qk_norm_mean"] = qk_norm_mean
    sz = reference.sizes_of(config)
    w = reference.init_weights(jax.random.PRNGKey(0), sz)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, sz["vocab"], (seq,)), jnp.int32)
    x = w["embedding"][tokens] * sz["embed_scale"]
    x, _, _ = reference.block(x, w["layers"][0], 0, sz)
    lw = w["layers"][1]
    out = reference.rms_norm(reference.attention(
        reference.rms_norm(x, lw["norm1"], sz["eps"]), lw, True, sz),
        lw["post_attn_norm"], sz["eps"])
    late = np.asarray(out)[window:]         # positions with a whole window
    shared = np.square(late.mean(0)).sum() / np.square(late).sum(-1).mean()
    assert low <= shared <= high


def test_trainer_matches_the_reference_over_three_adam_steps(sizes, mesh):
    """Loss, every leaf's gradient, and the parameters after three FusedAdam
    steps, against the reference's ``jax.grad`` and plain Adam."""
    sz = sizes
    cfg = trinity_program.transformer_config(sz, jnp.float32)
    init_fn, _, make_train_step = build_gpt_3d(
        cfg, num_microbatches=2, mesh=mesh)
    template, specs = init_fn(jax.random.PRNGKey(0), None)
    key = jax.random.PRNGKey(7)
    params = trinity_program.weights_maker(template, reference, sz)(key)
    weights = reference.init_weights(key, sz)
    rng = np.random.default_rng(0)
    batches = [jnp.asarray(rng.integers(0, sz["vocab"], (2, 128)), jnp.int32)
               for _ in range(3)]

    hyper = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8)
    opt = FusedAdam(lr=hyper["lr"], betas=(0.9, 0.999), eps=1e-8,
                    weight_decay=0.0)
    step = jax.jit(make_train_step(opt, specs, collect_stats=True))
    state = opt.init(params)
    logger = TrainStatsLogger(MetricRegistry())
    m = jax.tree_util.tree_map(jnp.zeros_like, weights)
    v = jax.tree_util.tree_map(jnp.zeros_like, weights)
    for t, tokens in enumerate(batches, start=1):
        params, state, loss, stats = step(params, state, tokens)
        # the step hands out its routers' choices and its pairs; the
        # reference follows the choices (float32 has near ties too: a choice
        # or two in four thousand may differ, by next to nothing)
        fetched = logger.fetch(stats)
        chosen = np.asarray(stats.moe_choices)
        assert chosen.shape == (2, 4, 128, sz["top_k"])
        want_loss, g, own, margin = reference.loss_and_grad(
            weights, tokens, sz, chosen=jnp.asarray(chosen))
        assert float(margin) < 1e-3
        assert np.sum(np.sort(chosen, -1) != np.sort(np.asarray(own), -1)) \
            <= 4
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-4)
        if t == 1:
            # Adam's first moment after one step is (1 - beta1) * gradient
            got = reference.compared_leaves(
                trinity_program.to_reference_names(
                    state.slots["exp_avg"]), sz)
            want = reference.compared_leaves(g, sz)
            assert sorted(got) == sorted(want)
            for name in want:
                scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
                np.testing.assert_allclose(
                    np.asarray(got[name]) / (0.1 * scale),
                    np.asarray(want[name]) / scale, atol=2e-4, err_msg=name)
            assert float(jnp.max(jnp.abs(got["L2.router_bias"]))) == 0.0
        weights, m, v = reference.adam_step(
            weights, g, m, v, jnp.float32(t), hyper["lr"], hyper["beta1"],
            hyper["beta2"], hyper["eps"])
        pairs = np.asarray(fetched["moe_pairs"])
        first, count = sz["held"]
        local = chosen - first
        assert pairs.shape == (2, 4, count)
        assert pairs.sum() == ((local >= 0) & (local < count)).sum()
    got = reference.compared_leaves(
        trinity_program.to_reference_names(params), sz)
    want = reference.compared_leaves(weights, sz)
    for name in want:
        # an element whose gradient is nought to rounding moves by the
        # learning rate either way each step (an embedding row no token of
        # a batch drew): a few such elements may differ by up to 3e-3, the
        # leaf as a whole may not
        gap = np.abs(np.asarray(got[name]) - np.asarray(want[name]))
        assert gap.max() < 2.5e-3 and gap.mean() < 1e-5, name
    registry = MetricRegistry()
    TrainStatsLogger(registry).log(3, stats)
    assert registry.gauge("train/moe_pairs").value == pairs.sum()
    peak = registry.gauge("train/moe_expert_load_peak").value
    assert peak == pytest.approx(
        float(np.mean(pairs.max(-1) / pairs.mean(-1))))


def altered(cfg, **changes):
    return dataclasses.replace(cfg, **changes)


@pytest.mark.parametrize("what,build", [
    ("pp > 1", lambda cfg, devices: (cfg, dict(pp=2))),
    ("tp > 1", lambda cfg, devices: (cfg, dict(tp=2))),
    ("packed_inputs", lambda cfg, devices: (cfg, dict(packed=True))),
    ("dropout", lambda cfg, devices: (altered(cfg, hidden_dropout=0.1), {})),
    ("sequence_parallel", lambda cfg, devices: (
        altered(cfg, sequence_parallel=True, tensor_axis="tp"), {})),
])
def test_trainer_refuses_by_name(sizes, what, build):
    cfg, how = build(trinity_program.transformer_config(sizes, jnp.float32),
                     jax.devices())
    n = how.get("pp", 1) * how.get("tp", 1)
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=how.get("tp", 1),
        pipeline_model_parallel_size=how.get("pp", 1),
        devices=jax.devices()[:n])
    try:
        with pytest.raises(NotImplementedError, match=what.split()[0]):
            build_gpt_3d(cfg, num_microbatches=2, mesh=mesh,
                         num_chunks=cfg.num_layers // how.get("pp", 1),
                         packed_inputs=how.get("packed", False))
    finally:
        parallel.destroy_model_parallel()


def test_serving_refuses_a_field_it_does_not_implement(sizes, mesh):
    from apex_tpu.serving import ServingConfig, ServingEngine

    cfg = trinity_program.transformer_config(sizes, jnp.float32)
    params = HybridParams(embedding=None, layers=(), final_norm=None,
                          head=None)
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(cfg, ServingConfig(
            max_batch=2, max_seq=64, prefill_len=16, n_blocks=16,
            prefix_caching=False), params)
    for name in ("full.rotary_dim=0", "window.qk_norm", "window.gate",
                 "sandwich_norm", "embedding_multiplier",
                 "experts.route_eps"):
        assert name in str(err.value), name
    # served since ISSUE 33 (tests/test_serving_latent.py serves them)
    for name in ("experts.shared_experts", "experts.route_scale"):
        assert name not in str(err.value), name


# ------------------------------------------------------------- the counts


def test_step_operation_count_by_hand():
    """``hybrid_train_mfu``'s count at the cell's sizes, worked by hand."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        sz = reference.sizes_of(json.load(f))
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512          # q, g, o; k, v
    met = (25088 * 2048 + 5 * attention + 3 * 2048 * 6144
           + 4 * (2048 * 128 + 3 * 2048 * 1024))
    assert hybrid_model_flops.always_met(sz) == met
    assert reference.count_params(sz) == met + 4 * 3 * 2048 * 1024
    assert reference.stored_params(sz) == pytest.approx(705.7e6, rel=1e-3)
    window_pairs = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert hybrid_attention.pairs_seen(8192, 2048) == window_pairs
    assert window_pairs / 8192 == pytest.approx(1792, rel=1e-3)
    assert hybrid_attention.pairs_seen(8192) == 8192 * 8193 // 2
    per_pair = 6 * 2 * 128 * 32 * 2                 # 6 products, 2 rows
    flops = (6 * met * 16384 + 18 * 2048 * 1024 * 65536
             + per_pair * (4 * window_pairs + 8192 * 8193 // 2))
    assert hybrid_model_flops.train_step_flops(sz, 2, 8192, 65536) == flops
    assert flops == pytest.approx(36.3e12, rel=0.01)
    flops, nbytes = moe_train.routed(1000, 30, 2048, 1024)
    assert flops == 1000 * 18 * 2048 * 1024
    assert nbytes == 3 * 30 * 3 * 2048 * 1024 * 2 + 6 * 1000 * 2048 * 2
    f, b = hybrid_attention.train_step(sz, False, 2, 8192)
    assert f == per_pair * 8192 * 8193 // 2
    assert b == 2 * ((6 * 32 + 6 * 4) * 8192 * 128 * 2 + 2 * 32 * 8192 * 4)
