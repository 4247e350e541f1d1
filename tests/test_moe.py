"""Switch-MoE + expert parallelism (parity-plus; the reference stubs MoE
out at ``standalone_transformer_lm.py:675``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import parallel
from apex_tpu.parallel import collectives as cc
from apex_tpu.transformer import moe
from apex_tpu.transformer.moe import SwitchMLP, switch_route

# the Switch layer's tests are slow ones; the router's (below) are not
slow = pytest.mark.slow

S, B, H, FFN, E = 8, 4, 16, 32, 4


@slow
def test_switch_route_properties():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, E))
    dispatch, gate, aux = switch_route(logits, capacity=16)
    d = np.asarray(dispatch)
    assert d.shape == (32, E, 16)
    # each token goes to at most one (expert, slot)
    assert (d.reshape(32, -1).sum(axis=1) <= 1).all()
    # no slot is double-booked
    assert (d.sum(axis=0) <= 1).all()
    # capacity 16 > 32/4: nothing dropped here
    assert d.sum() == 32
    assert float(aux) >= 1.0 - 1e-6  # E * sum f_e P_e >= 1 (Cauchy-Schwarz)
    np.testing.assert_allclose(
        np.asarray(gate),
        np.asarray(jax.nn.softmax(logits, -1).max(axis=-1)), rtol=1e-6)


@slow
def test_switch_route_capacity_drops():
    # all tokens want expert 0; capacity 2 keeps exactly the first 2
    logits = jnp.zeros((8, E)).at[:, 0].set(10.0)
    dispatch, _, _ = switch_route(logits, capacity=2)
    d = np.asarray(dispatch)
    assert d[:, 0].sum() == 2
    assert d[:2, 0].sum() == 2  # first-come-first-served (cumsum order)
    assert d[:, 1:].sum() == 0


@slow
def test_switch_mlp_matches_manual_expert_apply():
    """With ample capacity, the dispatch/combine einsums equal routing
    each token through its argmax expert directly."""
    m = SwitchMLP(hidden_size=H, ffn_size=FFN, num_experts=E,
                  capacity_factor=E * 1.0)  # capacity = T: nothing dropped
    x = jax.random.normal(jax.random.PRNGKey(1), (S, B, H))
    params = m.init(jax.random.PRNGKey(2), x)["params"]
    (y, aux), _ = m.apply({"params": params}, x, mutable=["losses"])

    p = jax.device_get(params)
    flat = np.asarray(x).reshape(-1, H)
    logits = flat @ p["router"]
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    idx = probs.argmax(-1)
    ref = np.zeros_like(flat)
    for t in range(flat.shape[0]):
        e = idx[t]
        hmid = np.asarray(jax.nn.gelu(
            jnp.asarray(flat[t] @ p["w1"][e] + p["b1"][e])))
        ref[t] = (hmid @ p["w2"][e] + p["b2"][e]) * probs[t, e]
    np.testing.assert_allclose(np.asarray(y).reshape(-1, H), ref,
                               rtol=2e-5, atol=2e-5)


def _moe_specs():
    return {"router": P(), "w1": P("cp"), "b1": P("cp"),
            "w2": P("cp"), "b2": P("cp")}


@slow
def test_expert_parallel_matches_dense():
    """EP over an 8-way axis == the dense path run on the gathered global
    expert stacks: each rank holds ONLY its E/ep experts (true memory
    sharding), tokens move via the all_to_all pair."""
    EP = 8
    parallel.initialize_model_parallel(context_parallel_size=EP)
    try:
        m_dense = SwitchMLP(hidden_size=H, ffn_size=FFN, num_experts=8,
                            capacity_factor=8.0)
        m_ep = SwitchMLP(hidden_size=H, ffn_size=FFN, num_experts=8,
                         capacity_factor=8.0, expert_axis="cp")
        x = jax.random.normal(jax.random.PRNGKey(3), (S, B * EP, H))
        specs = _moe_specs()

        # rank-folded init inside the shard_map: local [E/ep, ...] stacks
        params = cc.shard_over(
            lambda xb: m_ep.init(jax.random.PRNGKey(4), xb)["params"],
            in_specs=P(None, "cp"), out_specs=specs)(x)
        # local shards really are 1 expert per rank
        assert params["w1"].shape == (8, H, FFN)  # global view: 8 experts
        # expert groups decorrelated by the rank-folded init
        assert not np.allclose(np.asarray(params["w1"][0]),
                               np.asarray(params["w1"][1]))

        def local(p, xb):
            (y, aux), _ = m_ep.apply({"params": p}, xb, mutable=["losses"])
            return y

        y_ep = cc.shard_over(
            local, in_specs=(specs, P(None, "cp")),
            out_specs=P(None, "cp"))(params, x)

        # dense reference on the gathered global stacks (global arrays ARE
        # the concatenation of the local shards)
        (y_ref, _), _ = m_dense.apply(
            {"params": jax.device_get(params)}, x, mutable=["losses"])
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-5)

        # grads flow through the all_to_all pair and stay shard-local
        def loss(p, xb):
            y = cc.shard_over(
                local, in_specs=(specs, P(None, "cp")),
                out_specs=P(None, "cp"))(p, xb)
            return jnp.sum(y ** 2)

        g = jax.grad(loss)(params, x)
        g_ref = jax.grad(
            lambda p: jnp.sum(m_dense.apply({"params": p}, x,
                                            mutable=["losses"])[0][0] ** 2)
        )(jax.device_get(params))
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
    finally:
        parallel.destroy_model_parallel()


@slow
def test_moe_gpt_trains():
    """TransformerConfig.num_experts swaps the dense MLP for SwitchMLP and
    the LM still trains."""
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import GPTModel, TransformerConfig

    cfg = TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=4,
        padded_vocab_size=64, max_position_embeddings=16,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis=None,
        num_experts=4)
    model = GPTModel(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    # expert stacks exist in the tree
    leaf_paths = [p for p, _ in
                  jax.tree_util.tree_leaves_with_path(params)]
    assert any("router" in str(p) for p in leaf_paths)
    opt = FusedAdam(lr=1e-3)
    state = opt.init(params)

    from apex_tpu.transformer.moe import collect_moe_aux

    @jax.jit
    def step(p, s):
        def loss_fn(p):
            losses, mut = model.apply({"params": p}, tokens, labels=tokens,
                                      mutable=["losses"])
            aux = collect_moe_aux(mut)
            return jnp.mean(losses) + 1e-2 * aux, aux
        (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        p, s = opt.step(g, s, p)
        return p, s, l, aux

    losses = []
    for _ in range(15):
        params, state, l, aux = step(params, state)
        losses.append(float(l))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()
    assert float(aux) >= 1.0 - 1e-6  # the aux loss is real and in the objective


# ---------------------------- softmax scores and the group limit (ISSUE 33)


def _reference():
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import deepseek_v2

    return deepseek_v2


def _route_loop(logits, bias, top_k, n_groups, topk_groups, scoring,
                normalize, scale):
    """Token by token, expert by expert."""
    import math

    out_e, out_w = [], []
    for row in np.asarray(logits, np.float64):
        if scoring == "softmax":
            e = [math.exp(v - max(row)) for v in row]
            scores = [v / sum(e) for v in e]
        else:
            scores = [1.0 / (1.0 + math.exp(-v)) for v in row]
        by = [s + (0.0 if bias is None else float(bias[i]))
              for i, s in enumerate(scores)]
        size = len(row) // n_groups
        best = [max(by[g * size:(g + 1) * size]) for g in range(n_groups)]
        kept = sorted(range(n_groups), key=lambda g: (-best[g], g))[
            :topk_groups]
        allowed = [i for i in range(len(row)) if i // size in kept]
        chosen = sorted(allowed, key=lambda i: (-by[i], i))[:top_k]
        w = [scores[i] for i in chosen]
        if normalize:
            w = [v / sum(w) for v in w]
        out_e.append(chosen)
        out_w.append([scale * v for v in w])
    return np.asarray(out_e), np.asarray(out_w)


@pytest.mark.parametrize("scoring, groups, normalize, scale, biased", [
    ("softmax", (8, 3), False, 16.0, False),     # DeepSeek-V2's
    ("softmax", (4, 2), True, 1.0, False),
    ("sigmoid", (4, 1), True, 2.5, True),
    ("sigmoid", (1, 1), True, 1.0, True),        # the family that was there
    ("softmax", (1, 1), False, 1.0, False),
])
def test_route_topk_against_a_plain_loop(scoring, groups, normalize, scale,
                                         biased):
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(40, 16)).astype(np.float32) * 2.0
    # ties at both levels: whole groups alike, and experts alike in a group
    logits[0] = 0.0
    logits[1, :] = np.repeat(rng.normal(size=4), 4)
    logits[2, 4:8] = logits[2, 0:4]
    logits[3] = np.tile(rng.normal(size=2), 8)
    bias = (rng.normal(size=16).astype(np.float32) * 0.1 if biased else None)
    kw = {}
    if scoring != "sigmoid":
        kw["scoring"] = scoring
    if groups != (1, 1):
        kw["groups"] = groups
    if not normalize:
        kw["normalize"] = False
    experts, weights = moe.route_topk(
        jnp.asarray(logits), None if bias is None else jnp.asarray(bias), 3,
        0.0, scale, **kw)
    want_e, want_w = _route_loop(logits, bias, 3, *groups, scoring,
                                 normalize, scale)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(weights), want_w, rtol=2e-5)
    # every chosen expert lies in a kept group
    kept = np.asarray(moe.kept_groups(
        jnp.asarray(_scores(logits, scoring) + (0 if bias is None else bias)),
        *groups))
    size = 16 // groups[0]
    assert kept.sum(1).tolist() == [groups[1]] * 40
    assert np.take_along_axis(kept, np.asarray(experts) // size, 1).all()


def _scores(logits, scoring):
    x = jnp.asarray(logits)
    return np.asarray(jax.nn.softmax(x, -1) if scoring == "softmax"
                      else jax.nn.sigmoid(x))


def test_route_topk_agrees_with_the_reference_router():
    ref = _reference()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(50, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 160)) * 0.3, jnp.float32)
    logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
    experts, weights = moe.route_topk(logits, None, 6, 0.0, 16.0,
                                      scoring="softmax", groups=(8, 3),
                                      normalize=False)
    want_e, want_w, own, margin = ref.route(x, router, 6, 8, 3, 16.0)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(want_e))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want_w),
                               rtol=1e-5)
    assert float(jnp.max(margin)) == 0.0
    # at most three groups of twenty hold a token's six experts
    assert max(len(set(row // 20)) for row in np.asarray(experts)) <= 3


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each of eight chips routes over all 16 experts in 8 groups, keeps 3
    groups, and adds its own group's experts; the shared SwiGLU is counted
    once.  The eight partial results add up to the uncut reference's layer,
    and each program share equals the reference's share."""
    ref = _reference()
    sz = {"hidden": 32, "layers": 2, "experts": (0, 1), "expert_ffn": 16,
          "dense_ffn": 48, "n_experts": 16, "held": (0, 16), "top_k": 3,
          "n_group": 8, "topk_group": 3, "route_scale": 16.0, "shared": 2,
          "kinds": ({"heads": 2, "q_rank": 8, "kv_rank": 8, "k_dim": 8,
                     "nope": 4, "rope": 4, "v_dim": 4},),
          "init": {"std": 0.2, "norm_std": 0.1, "expert_gain": 4.0,
                   "router_gain": 4.0}}
    key = jax.random.PRNGKey(7)
    lw = {name: mean + std * jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32)
        for i, (name, (shape, std, mean)) in enumerate(
            sorted(ref.layer_shapes(sz, 1).items()))}
    x = jax.random.normal(jax.random.fold_in(key, 99), (48, sz["hidden"]))
    whole = np.asarray(ref.expert_layer(x, lw, sz))
    only_shared = np.asarray(ref.swiglu(x, lw["shared_gate_up"],
                                        lw["shared_down"]))
    total, reached = np.zeros_like(whole), 0
    for first in range(0, 16, 2):
        mine = slice(first, first + 2)
        share = dict(lw, experts_gate_up=lw["experts_gate_up"][mine],
                     experts_down=lw["experts_down"][mine])
        want = np.asarray(ref.expert_layer(x, share, sz, held=(first, 2),
                                           shared=first == 0))
        got, pairs, chosen, tokens = moe.held_experts_ffn(
            x, share["router"], None, share["experts_gate_up"],
            share["experts_down"], top_k=3, held=(first, 2),
            route_scale=16.0, scoring="softmax", groups=(8, 3),
            normalize=False,
            shared=((share["shared_gate_up"], share["shared_down"])
                    if first == 0 else None))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)
        own = np.asarray(chosen) // 2 == first // 2
        assert int(pairs.sum()) == int(own.sum())
        # a pair comes only from a token that kept this chip's group
        assert int(own.any(1).sum()) <= int(tokens) <= 48
        reached += int(tokens)
        total += np.asarray(got)
    assert reached == 3 * 48            # every token kept three of eight
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=2e-5)
    assert float(np.abs(whole - only_shared).max()) > 1e-2
