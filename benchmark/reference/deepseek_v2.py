"""Plain reference of DeepSeek-V2's language model (arXiv:2405.04434): the
forward pass in straightforward float32 ``jax.numpy``, no cache, no
kernels, no batching, and nothing of ``apex_tpu``.  Every matrix
multiplication runs at ``highest`` precision (on a TPU a float32 matmul is
otherwise done in bfloat16 passes).

The layer equations, from the model's public ``config.json`` (the keys named
in brackets) and the source's ``modeling_deepseek.py``:

- Pre-norm residual block ``h = x + MLA(RMSNorm(x))``, ``y = h +
  F(RMSNorm(h))``; RMSNorm with ``rms_norm_eps`` and float32 statistics; a
  final RMSNorm and an untied head.  No biases (``attention_bias`` false).
  ``F`` is a dense SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers and the expert layer elsewhere
  (``moe_layer_freq`` 1).
- **Multi-head latent attention, in the expanded form.**  For a token's
  normed input ``u`` at position ``t``: ``c_q = RMSNorm(u W_qa)``
  (``q_lora_rank``), ``[q_nope_i | q_rope_i] = c_q W_qb`` per head ``i`` of
  ``num_attention_heads`` (``qk_nope_head_dim`` beside
  ``qk_rope_head_dim``); ``[c_kv | k_r] = u W_kva`` (``kv_lora_rank`` beside
  ``qk_rope_head_dim``), ``c = RMSNorm(c_kv)``; per head ``k_nope_i = c
  W_UK_i`` and ``v_i = c W_UV_i`` (``v_head_dim``), the two halves of the
  checkpoint's ``kv_b_proj``; one rotary key ``k_rope = R_t(k_r)`` for all
  heads and ``q_rope_i <- R_t(q_rope_i)``; ``score_ij = s (q_nope_i .
  k_nope_ij + q_rope_i . k_rope_j)`` for ``j <= t``, softmax in float32,
  ``o_i = sum_j p_ij v_ij``, output ``concat_i(o_i) W_o``.  ``s =
  (nope + rope) ** -0.5 * m ** 2`` with ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1`` (``rope_scaling``).  The program decodes from a cache of
  ``[c | k_rope]`` in the *absorbed* form (``W_UK`` multiplied into the
  query, ``W_UV`` applied after the softmax): equal in exact arithmetic, and
  independent of this file's mathematics.
- ``R_t`` is YaRN as ``DeepseekV2YarnRotaryEmbedding`` has it, on
  ``qk_rope_head_dim`` channels, base ``rope_theta``: :func:`yarn_inv_freq`;
  the tables' factor ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)`` is 1 at the published values.
- **Expert layer**, for normed ``u``: ``sigma = softmax(u W_r)`` over all the
  published ``n_routed_experts`` in float32 (``scoring_func``); the score of
  each of ``n_group`` groups of consecutive experts is its best ``sigma``;
  the ``topk_group`` best groups are kept; among their experts the
  ``num_experts_per_tok`` of largest ``sigma`` are chosen (ties to the lower
  id; ``topk_method`` ``group_limited_greedy``); weight ``w_e =
  routed_scaling_factor * sigma_e`` (``norm_topk_prob`` false); ``y =
  SwiGLU_shared(u) + sum_e w_e SwiGLU_e(u)``, the shared one of width
  ``n_shared_experts * moe_intermediate_size``.  No auxiliary loss when
  serving.

Read from the configuration and stated under its ``assumed``: rotary pairs
channel ``2 t`` with ``2 t + 1`` and is applied to the pairs where they lie
(the source pulls the pairs apart first; a permutation of channels shared by
q and k, which no score sees).

Departures from the checkpoint's layout, none in the arithmetic: linear
weights are stored ``[in, out]``; ``kv_b_proj`` is kept as its two halves
per head (``w_uk [heads, rank, nope]``, ``w_uv [heads, rank, v]``); an
expert's gate and up matrices lie side by side (``[hidden, 2 f]``, gate
columns first), as do the dense layer's and the shared experts' (the
``n_shared_experts`` as one SwiGLU, which is what their sum is).

**The chip's share.**  The configuration file holds one chip's share of a
stated deployment: ``n_routed_experts`` there counts the experts *held*
(``share.experts_first`` on), ``vocab_size`` the rows of the vocabulary
held.  The reference is given the same share: it routes over all the
published experts and adds the held experts' outputs only; what the absent
experts would add is left out, and that partial result goes on to the next
layer.  The shared experts are computed here for this chip's tokens.
:func:`expert_layer` takes the share as an argument, so that a test can add
the shares up against the uncut layer.

The weights are seeded, held at the values the configuration states (rounded
to bfloat16); a layer at a time is computed in float32, attention a block
of heads and a block of rows at a time and each expert over the rows routed
to it, so that the model at its published widths and a sequence of twenty
thousand tokens fit one chip.  ``quant`` turns the reference into its own
low-precision control: the operands of every layer GEMM rounded to fp8;
``None`` is the reference itself.

**Near ties in the router.**  Scores near the cut lie closer together than
bfloat16 rounds a hidden state, at both levels: the third and fourth group,
the sixth and seventh expert.  A comparison may hand the reference the
program's choices (``routing``, :func:`hidden_states`): it takes them in
place of its own, weighs them by its own scores, and reports by how much
the program's choice lay under its own cut at each level (``margin``, the
larger of the two), which the comparison holds to a limit: a choice that is
no near tie is a fault, and is not followed silently.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256          # query rows of attention computed at once
HEAD_BLOCK = 16          # heads whose keys and values are expanded at once
SEQ_BUCKET = 1024        # a sequence is padded to a multiple of this


def sizes_of(config: dict) -> dict:
    """The reference's sizes from the configuration file."""
    if (config["scoring_func"] != "softmax"
            or config["topk_method"] != "group_limited_greedy"
            or config["norm_topk_prob"] or config["attention_bias"]
            or config["hidden_act"] != "silu" or config["moe_layer_freq"] != 1
            or config["tie_word_embeddings"]
            or config["rope_scaling"]["type"] != "yarn"):
        raise ValueError("the reference does not follow this configuration")
    layers = config["num_hidden_layers"]
    share = config["share"]
    n_experts = config["source_values"]["n_routed_experts"]
    if n_experts % config["n_group"]:
        raise ValueError("the groups do not divide the experts")
    kind = {"heads": config["num_attention_heads"], "kv_heads": 1,
            "k_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            "v_dim": config["v_head_dim"], "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"],
            "q_rank": config["q_lora_rank"],
            "kv_rank": config["kv_lora_rank"],
            "theta": float(config["rope_theta"]), "window": None,
            "sink": False}
    return {
        "hidden": config["hidden_size"], "layers": layers,
        "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
        "kinds": (kind,), "pattern": (0,) * layers,
        "experts": tuple(int(layer >= config["first_k_dense_replace"])
                         for layer in range(layers)),
        "dense_ffn": config["intermediate_size"],
        "expert_ffn": config["moe_intermediate_size"],
        "n_experts": n_experts,
        "held": (share["experts_first"], config["n_routed_experts"]),
        "top_k": config["num_experts_per_tok"],
        "n_group": config["n_group"], "topk_group": config["topk_group"],
        "route_scale": float(config["routed_scaling_factor"]),
        "shared": config["n_shared_experts"],
        "yarn": {k: v for k, v in config["rope_scaling"].items()
                 if k != "type"},
        "init": dict(config["assumed"]["init"]),
    }


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(sz: dict, fault=None) -> float:
    """``k_dim ** -0.5 * m ** 2``: YaRN's correction of the attention
    temperature at the stretched context."""
    yarn = sz["yarn"]
    m = 1.0 if fault == "yarn_scale_left_out" else yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"])
    return sz["kinds"][0]["k_dim"] ** -0.5 * m * m


def yarn_inv_freq(dim: int, base: float, yarn: dict) -> np.ndarray:
    """``dim / 2`` inverse frequencies: ``f_extra = base ** (-2 k / dim)``
    where the pair turns more than ``beta_fast`` times over the original
    context, ``f_extra / factor`` where fewer than ``beta_slow`` times, a
    linear blend between."""
    def correction_dim(rotations):
        return (dim * math.log(yarn["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    k = np.arange(dim // 2, dtype=np.float64)
    f_extra = base ** (-2.0 * k / dim)
    f_inter = f_extra / yarn["factor"]
    mask = 1.0 - np.clip((k - low) / (high - low), 0.0, 1.0)
    return (f_inter * (1.0 - mask) + f_extra * mask).astype(np.float32)


def _attention_params(sz):
    k, h = sz["kinds"][0], sz["hidden"]
    return (h * k["q_rank"] + k["q_rank"] * k["heads"] * k["k_dim"]
            + h * (k["kv_rank"] + k["rope"])
            + k["kv_rank"] * k["heads"] * (k["nope"] + k["v_dim"])
            + k["heads"] * k["v_dim"] * h)


def count_params(sz: dict, positions: bool = False) -> int:
    """Parameters one token's matrix multiplications meet on this chip:
    attention, the dense layer, the routers, the shared experts, the head,
    and of each expert layer the share ``top_k * held / n_experts`` of one
    expert that a token expects to find here.  The token table is a gather
    and is not counted; the model has no position table."""
    del positions
    h = sz["hidden"]
    expert = 3 * h * sz["expert_ffn"]
    n = sz["vocab"] * h
    for moe in sz["experts"]:
        n += _attention_params(sz)
        if moe:
            n += h * sz["n_experts"] + sz["shared"] * expert + round(
                expert * sz["top_k"] * sz["held"][1] / sz["n_experts"])
        else:
            n += 3 * h * sz["dense_ffn"]
    return n


def stored_params(sz: dict) -> int:
    """Parameters the chip holds: every held expert whole, both tables."""
    h, k = sz["hidden"], sz["kinds"][0]
    expert = 3 * h * sz["expert_ffn"]
    n = 2 * sz["vocab"] * h + h
    for moe in sz["experts"]:
        n += _attention_params(sz) + 2 * h + k["q_rank"] + k["kv_rank"]
        if moe:
            n += (h * sz["n_experts"]
                  + (sz["shared"] + sz["held"][1]) * expert)
        else:
            n += 3 * h * sz["dense_ffn"]
    return n


# ------------------------------------------------------------------ weights


def layer_shapes(sz: dict, layer: int) -> dict:
    """``{name: (shape, std, mean)}`` of one layer's weights.  Matrices are
    N(0, std); those that face the residual stream are scaled by
    ``1 / sqrt(2 L)``, the held experts' by ``expert_gain`` more (this chip
    adds an eighth of the experts: at the plain scale what a routed expert
    adds would hide under the shared experts' output).  The router's are
    scaled by ``router_gain``: softmax scores of plain N(0, std) logits are
    too flat for a choice of six to mean anything.  Norm gains are 1 +
    N(0, norm_std): a checkpoint's are not all one, and such gains would
    hide a dropped inner norm's gain."""
    init = sz["init"]
    h, std = sz["hidden"], init["std"]
    out_std = std / math.sqrt(2.0 * sz["layers"])
    k = sz["kinds"][0]
    n = k["heads"]
    gain = (init["norm_std"], 1.0)
    shapes = {
        "norm1": ((h,),) + gain, "norm2": ((h,),) + gain,
        "wq_a": ((h, k["q_rank"]), std, 0.0),
        "q_a_norm": ((k["q_rank"],),) + gain,
        "wq_b": ((k["q_rank"], n * k["k_dim"]), std, 0.0),
        "wkv_a": ((h, k["kv_rank"] + k["rope"]), std, 0.0),
        "kv_a_norm": ((k["kv_rank"],),) + gain,
        "w_uk": ((n, k["kv_rank"], k["nope"]), std, 0.0),
        "w_uv": ((n, k["kv_rank"], k["v_dim"]), std, 0.0),
        "wo": ((n * k["v_dim"], h), out_std, 0.0),
    }
    if sz["experts"][layer]:
        f, held = sz["expert_ffn"], sz["held"][1]
        shapes.update({
            "router": ((h, sz["n_experts"]), std * init["router_gain"], 0.0),
            "experts_gate_up": ((held, h, 2 * f), std, 0.0),
            "experts_down": ((held, f, h),
                             out_std * init["expert_gain"], 0.0),
            "shared_gate_up": ((h, 2 * sz["shared"] * f), std, 0.0),
            "shared_down": ((sz["shared"] * f, h), out_std, 0.0)})
    else:
        f = sz["dense_ffn"]
        shapes.update({"ffn_gate_up": ((h, 2 * f), std, 0.0),
                       "ffn_down": ((f, h), out_std, 0.0)})
    return shapes


@functools.partial(jax.jit, static_argnames=("shape", "std", "mean"))
def _leaf(key, shape, std, mean):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


def init_weights(key, sz: dict) -> dict:
    """Seeded weights, rounded to bfloat16, one leaf at a time (a leaf's
    float32 draft is the largest temporary).  ``{"embedding" [V, h], "head"
    [h, V], "final_norm" [h], "layers": [one dict per layer]}``."""
    init = sz["init"]
    top = {"embedding": ((sz["vocab"], sz["hidden"]), init["std"], 0.0),
           "head": ((sz["hidden"], sz["vocab"]), init["std"], 0.0),
           "final_norm": ((sz["hidden"],), init["norm_std"], 1.0)}
    w = {name: _leaf(jax.random.fold_in(key, i), *spec)
         for i, (name, spec) in enumerate(sorted(top.items()))}
    w["layers"] = []
    for layer in range(sz["layers"]):
        lkey = jax.random.fold_in(key, 1000 + layer)
        w["layers"].append({
            name: _leaf(jax.random.fold_in(lkey, i), *spec)
            for i, (name, spec) in enumerate(
                sorted(layer_shapes(sz, layer).items()))})
    return w


# -------------------------------------------------------------- the control


def fp_quant(exponent_bits: int, mantissa_bits: int, max_value: float):
    """Per-tensor scaled rounding to a small float format (amax scaling, as
    fp8 recipes do), returned in float32."""
    def quant(x):
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        s = max_value / amax
        return jax.lax.reduce_precision(x * s, exponent_bits,
                                        mantissa_bits) / s
    return quant


Quant = collections.namedtuple("Quant", "fwd")
FP8 = Quant(fwd=fp_quant(4, 3, 240.0))      # e4m3, the forward GEMMs' format
# the configuration's own precision, for counting what rounding alone does
BF16 = Quant(fwd=lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))


def _operand(x, quant):
    """A GEMM operand in float32; ``quant`` rounds it first (the control)."""
    x = x.astype(jnp.float32)
    return x if quant is None else quant.fwd(x)


def _matmul(x, w, quant):
    """``x [rows, in] @ w [in, out]`` in float32 at ``highest`` precision."""
    return jnp.dot(_operand(x, quant), _operand(w, quant), precision=HIGHEST)


# ---------------------------------------------------------------- the layers


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain.astype(jnp.float32)


def rotate(x, positions, inv_freq):
    """YaRN rotary on all of ``x [s, heads, d]``: channel ``2 t`` pairs with
    ``2 t + 1`` and both stay where they lie."""
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(x, lw, sz, quant=None, fault=None):
    """One latent attention layer on ``x [s, hidden]`` (already normed), in
    the expanded form: every head's keys and values are made from the
    latent, ``HEAD_BLOCK`` heads and ``ROW_BLOCK`` query rows at a time.
    ``fault`` plants what a program can get wrong, for the tests that show
    the comparison catches it."""
    k = sz["kinds"][0]
    s, n = x.shape[0], k["heads"]
    nope, rope, rank, dv = k["nope"], k["rope"], k["kv_rank"], k["v_dim"]
    positions = jnp.arange(s)
    inv_freq = jnp.asarray(yarn_inv_freq(rope, k["theta"], sz["yarn"]))
    yarn = sz["yarn"]
    # the tables' own factor: 1 at the published values
    gain = (yarn_mscale(yarn["factor"], yarn["mscale"])
            / yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]))
    scale = softmax_scale(sz, fault)

    c_q = _operand(rms_norm(_matmul(x, lw["wq_a"], quant), lw["q_a_norm"],
                            sz["eps"]), quant)
    c_kv = _matmul(x, lw["wkv_a"], quant)
    c = c_kv[:, :rank]
    if fault != "latent_norm_left_out":
        c = rms_norm(c, lw["kv_a_norm"], sz["eps"])
    k_rope = gain * rotate(c_kv[:, None, rank:], positions, inv_freq)[:, 0]
    source = c                  # what the values are expanded from
    if fault == "values_from_whole_row":
        # the values' channels taken ``rope`` lanes late in the cached row
        # ``[c | k_rope]``: they span the key's lanes
        source = jnp.concatenate([c, k_rope], -1)[:, rope:]
    c, source = _operand(c, quant), _operand(source, quant)
    blocks = n // HEAD_BLOCK if n % HEAD_BLOCK == 0 else 1
    hb = n // blocks
    wq_b = _operand(lw["wq_b"], quant).reshape(-1, blocks, hb * k["k_dim"])
    w_uk = _operand(lw["w_uk"], quant).reshape(blocks, hb, rank, nope)
    w_uv = _operand(lw["w_uv"], quant).reshape(blocks, hb, rank, dv)

    def heads(block):
        wq, uk, uv = block
        q = jnp.dot(c_q, wq, precision=HIGHEST).reshape(s, hb, k["k_dim"])
        q_nope = q[..., :nope]
        q_rope = gain * rotate(q[..., nope:], positions, inv_freq)
        k_nope = jnp.einsum("sc,ncd->snd", c, uk, precision=HIGHEST)
        v = jnp.einsum("sc,ncd->snd", source, uv, precision=HIGHEST)

        def rows(start):
            i = start + jnp.arange(ROW_BLOCK)[:, None]
            qn = jax.lax.dynamic_slice_in_dim(q_nope, start, ROW_BLOCK, 0)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, start, ROW_BLOCK, 0)
            a = jnp.einsum("qnd,knd->nqk", qn, k_nope, precision=HIGHEST)
            if fault != "rope_term_left_out":
                a = a + jnp.einsum("qnd,kd->nqk", qr, k_rope,
                                   precision=HIGHEST)
            a = jnp.where((positions[None, :] <= i)[None], a * scale,
                          -jnp.inf)
            p = jax.nn.softmax(a, axis=-1)
            return jnp.einsum("nqk,knd->qnd", p, v, precision=HIGHEST)

        return jax.lax.map(rows, jnp.arange(0, s, ROW_BLOCK)).reshape(
            s, hb, dv)

    ctx = jax.lax.map(heads, (jnp.moveaxis(wq_b, 1, 0), w_uk, w_uv))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(s, n * dv)
    return _matmul(ctx, lw["wo"], quant)


def swiglu(x, gate_up, down, quant=None):
    f = down.shape[0]
    gu = _matmul(x, gate_up, quant)
    return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, quant)


@functools.partial(jax.jit, static_argnames=("top_k", "n_group", "topk_group",
                                             "route_scale", "fault"))
def route(x, router, top_k, n_group, topk_group, route_scale, fault=None,
          chosen=None):
    """``(experts [s, k], weights [s, k], own [s, k], margin [s])`` of
    normed ``x [s, hidden]``: float32 logits over all the experts, softmax
    scores, the ``topk_group`` groups of largest best score, among their
    experts the ``top_k`` of largest score (ties to the lower id at both
    levels), weights ``route_scale`` times the chosen scores.

    ``chosen [s, k]``, where given, are the experts a program chose: a row
    of it that is not ``-1`` takes the place of the reference's ``own``
    choice in ``experts``, weighed by the reference's own scores.  Near the
    cuts the scores lie closer together than a lower precision rounds, so a
    sound program's choice differs now and then, by a near tie, and the
    token then meets another expert: the comparison follows the program's
    choice and holds it to ``margin``, the larger of how far the best score
    of a group the program's choice lies in is under the reference's own
    group cut, and how far the lowest score among the program's choice lies
    under the sixth score within the groups the program may have kept, each
    as a difference of the scores' logarithms, which is the difference of
    the two logits (nought where the choices are one)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    s, n_experts = scores.shape
    size = n_experts // n_group
    best = jnp.max(scores.reshape(s, n_group, size), axis=-1)
    kept = jnp.argsort(-best, axis=-1, stable=True)[:, :topk_group]
    allowed = jnp.any(
        kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
    chosen_by = scores
    if fault != "group_limit_left_out":
        chosen_by = jnp.where(jnp.repeat(allowed, size, axis=1), scores, 0.0)
    own = jnp.argsort(-chosen_by, axis=-1, stable=True)[:, :top_k]
    experts = own
    if chosen is not None:
        experts = jnp.where(chosen[:, :1] >= 0, chosen, own)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    # the group level: the worst group the choice lies in against the
    # reference's own third group
    groups = jnp.arange(n_group)[None, None, :]
    group_cut = jnp.take_along_axis(best, kept[:, -1:], axis=-1)[:, 0]
    used_best = jnp.take_along_axis(best, experts // size, axis=-1)
    # the expert level, given the groups: a program that kept another
    # third group by a near tie fills its six from other experts, so the
    # cut is the sixth score within the groups it may have kept (those its
    # choice lies in, filled up with the reference's best)
    used = jnp.any((experts // size)[:, :, None] == groups, axis=1)
    may = jnp.argsort(-jnp.where(used, best + 2.0, best), axis=-1,
                      stable=True)[:, :topk_group]
    within = jnp.where(
        jnp.repeat(jnp.any(may[:, :, None] == groups, axis=1), size, axis=1),
        scores, 0.0)
    expert_cut = -jnp.sort(-within, axis=-1)[:, top_k - 1]
    # as differences of logarithms: softmax scores are small numbers whose
    # size swings with how peaked a token's distribution is, and the
    # logarithm's difference is the difference of the two logits
    tiny = jnp.finfo(jnp.float32).tiny
    under = lambda cut, used: (jnp.log(jnp.maximum(cut, tiny))  # noqa: E731
                               - jnp.log(jnp.maximum(jnp.min(used, -1), tiny)))
    margin = jnp.maximum(under(group_cut, used_best),
                         under(expert_cut, picked))
    if fault != "route_scale_left_out":
        picked = route_scale * picked
    return experts, picked, own, jnp.maximum(margin, 0.0)


@functools.partial(jax.jit, static_argnames=("first", "cap", "quant"))
def _held_experts(x, experts, weights, gate_up, down, first, cap, quant):
    """Sum over the held experts (``gate_up [count, h, 2 f]``, ``down
    [count, f, h]``, ids ``first`` on) of weight times expert output, each
    expert over the at most ``cap`` rows of ``x [s, h]`` routed to it."""
    s = x.shape[0]
    padded = jnp.concatenate([x, jnp.zeros_like(x[:1])])

    def one(y, e):
        hit = experts == first + e                            # [s, k]
        rows = jnp.nonzero(jnp.any(hit, -1), size=cap, fill_value=s)[0]
        w = jnp.sum(jnp.where(hit, weights, 0.0), -1)
        w = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])[rows]
        out = swiglu(padded[rows], gate_up[e], down[e], quant) * w[:, None]
        return y.at[rows].add(out, mode="drop"), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(gate_up.shape[0]))
    return y


_swiglu = jax.jit(swiglu, static_argnames=("quant",))


def expert_layer(x, lw, sz, held=None, quant=None, fault=None, chosen=None,
                 log=None, shared=True):
    """The share ``held = (first, count)`` of an expert feed-forward on
    normed ``x [s, hidden]``: routed over all the experts, the held ones'
    weighted outputs added (``lw["experts_*"]`` hold the share's experts),
    and, with ``shared``, the shared experts' SwiGLU (a test that adds the
    shares up counts it once).  Each expert is computed over the rows
    routed to it: a static ``cap`` of them, the smallest of ``s / 8``, ``s /
    2`` and ``s`` that the busiest held expert's rows fit (counted first,
    so no row is ever dropped).  ``chosen`` as in :func:`route`; ``log``, a
    list, is given the layer's ``(own choices [s, k], margin [s])``."""
    first, count = sz["held"] if held is None else held
    s = x.shape[0]
    experts, weights, own, margin = route(
        x, lw["router"], sz["top_k"], sz["n_group"], sz["topk_group"],
        sz["route_scale"], fault, chosen)
    if log is not None:
        log.append((np.asarray(own), np.asarray(margin)))
    local = np.asarray(experts) - first
    busiest = max(np.bincount(local[(local >= 0) & (local < count)],
                              minlength=1))
    cap = next(c for c in (max(s // 8, 1), max(s // 2, 1), s)
               if c >= busiest)
    y = _held_experts(x, experts, weights, lw["experts_gate_up"],
                      lw["experts_down"], first, cap, quant)
    if shared and fault != "shared_left_out":
        y = y + _swiglu(x, lw["shared_gate_up"], lw["shared_down"], quant)
    return y


@functools.partial(jax.jit, static_argnames=("sz_items", "quant", "fault"))
def _attention_block(x, lw, sz_items, quant, fault):
    sz = dict(sz_items)
    sz["kinds"] = (dict(sz["kinds"]),)
    sz["yarn"] = dict(sz["yarn"])
    h = x + attention(rms_norm(x, lw["norm1"], sz["eps"]), lw, sz, quant,
                      fault)
    return h, rms_norm(h, lw["norm2"], sz["eps"])


ATTENTION = ("norm1", "norm2", "wq_a", "q_a_norm", "wq_b", "wkv_a",
             "kv_a_norm", "w_uk", "w_uv", "wo")


def hidden_states(w, tokens, sz, quant=None, fault=None, routing=None):
    """Final-normed hidden states ``[s, hidden]`` of one sequence ``tokens
    [s]`` (``s`` a multiple of ``ROW_BLOCK``: :func:`_padded` gives one;
    causal, so padding behind changes nothing).

    ``routing``, a dict, is the record of the expert layers' choices, in
    and out.  In: ``routing["chosen"] [expert layers, n, k]``, where there,
    are the experts a program chose for the first ``n`` tokens, taken in
    place of the reference's own (:func:`route`).  Out: ``routing["own"]
    [expert layers, s, k]``, the choices of this pass itself, and
    ``routing["margin"]``, the largest margin by which a followed choice
    lay under this pass's own cuts."""
    small = tuple(sorted(
        [("eps", sz["eps"]),
         ("kinds", tuple(sorted(sz["kinds"][0].items()))),
         ("yarn", tuple(sorted(sz["yarn"].items())))]))
    chosen = None if routing is None else routing.get("chosen")
    if chosen is not None:
        chosen = np.asarray(chosen, np.int32)
        chosen = np.concatenate(
            [chosen, np.full((chosen.shape[0], len(tokens) - chosen.shape[1],
                              chosen.shape[2]), -1, np.int32)], 1)
    log = None if routing is None else []
    x = w["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    for layer, lw in enumerate(w["layers"]):
        h, normed = _attention_block(x, {k: lw[k] for k in ATTENTION}, small,
                                     quant, fault)
        if sz["experts"][layer]:
            x = h + expert_layer(
                normed, lw, sz, quant=quant, fault=fault, log=log,
                chosen=None if chosen is None else chosen[len(log)])
        else:
            x = h + _swiglu(normed, lw["ffn_gate_up"], lw["ffn_down"], quant)
    if routing is not None:
        routing["own"] = np.stack([own for own, _ in log])
        routing["margin"] = max(
            [0.0] + [float(np.max(m)) for _, m in log if chosen is not None])
    return jax.jit(rms_norm, static_argnums=2)(x, w["final_norm"], sz["eps"])


def _padded(tokens, pad=None):
    """``tokens`` padded behind to ``pad`` where given, else to a multiple
    of ``SEQ_BUCKET`` (short ones to a multiple of ``ROW_BLOCK``): few
    lengths, so few compilations."""
    step = SEQ_BUCKET if len(tokens) > SEQ_BUCKET // 2 else ROW_BLOCK
    size = -(-len(tokens) // step) * step
    if pad is not None and pad >= len(tokens):
        size = -(-pad // ROW_BLOCK) * ROW_BLOCK
    out = np.zeros((size,), np.int32)
    out[:len(tokens)] = tokens
    return out


@jax.jit
def _head(rows, head):
    return jnp.dot(rows, head.astype(jnp.float32), precision=HIGHEST)


def served_logits(w, prompt, served, sz, pad=None, quant=None, fault=None,
                  routing=None):
    """Logits ``[len(served), vocab]`` at the positions that produced
    ``served`` after ``prompt``, the served tokens fed back."""
    n = len(prompt) + len(served) - 1
    seq = np.concatenate([prompt, served])[:n]
    hidden = hidden_states(w, _padded(seq, pad), sz, quant, fault, routing)
    return _head(hidden[len(prompt) - 1: n], w["head"])


def served_token_gaps(w, prompt, served, sz, pad=None, routing=None):
    """For each greedy served token, how far its logit lies below the
    reference's best at its position; 0 where the reference agrees.
    ``routing`` as in :func:`hidden_states`."""
    rows = served_logits(w, prompt, served, sz, pad, routing=routing)
    picked = jnp.take_along_axis(rows, jnp.asarray(served)[:, None], 1)[:, 0]
    return jnp.max(rows, axis=-1) - picked


def last_logits(w, sequences, sz, pad=None, quant=None, fault=None,
                routing=None):
    """Logits ``[n, vocab]`` that follow each of ``sequences`` (lists of
    token ids): one forward pass each, every sequence padded to ``pad``
    where given (one shape, one compilation).  ``routing``: one record per
    sequence, as in :func:`hidden_states`."""
    routing = [None] * len(sequences) if routing is None else routing
    rows = [hidden_states(w, _padded(seq, pad), sz, quant, fault,
                          r)[len(seq) - 1]
            for seq, r in zip(sequences, routing)]
    return _head(jnp.stack(rows), w["head"])
