"""Plain reference of GLM-5's language model (``glm_moe_dsa``): the forward
pass in straightforward float32 ``jax.numpy``, no cache, no kernels, no
batching, and nothing of ``apex_tpu``.  Every matrix multiplication runs at
``highest`` precision (on a TPU a float32 matmul is otherwise done in
bfloat16 passes).

The layer equations, from the model's public ``config.json`` (the keys named
in brackets), DeepSeek-V3's ``modeling_deepseek.py`` for the latent
attention and the router, and DeepSeek-V3.2's ``Indexer`` (which
``glm_moe_dsa`` takes over) for the sparse selection:

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
  k_nope_ij + q_rope_i . k_rope_j)`` for the ``j`` the indexer selected for
  ``t`` and ``-inf`` elsewhere, softmax in float32, ``o_i = sum_j p_ij
  v_ij``, output ``concat_i(o_i) W_o``; ``s = (nope + rope) ** -0.5``
  (``qk_head_dim`` 256; no YaRN).  ``R_t`` is plain RoPE of base
  ``rope_theta`` on channel pairs ``(2 t, 2 t + 1)`` (``rope_interleave``).
  The program attends from a cache of ``[c | k_rope]`` in the *absorbed*
  form: equal in exact arithmetic, independent of this file's mathematics.
- **The indexer** (``index_n_heads`` ``H``, ``index_head_dim`` ``D``,
  ``index_topk`` ``k``): ``qI_th = (c_q W_Iq)_h``; one key a token ``kI_s =
  LayerNorm(u_s W_Ik)`` with a gain and a bias; RoPE (base ``rope_theta``)
  on the leading ``qk_rope_head_dim`` channels of both, pairs ``(2 t, 2 t +
  1)`` (``indexer_rope_interleave``); ``w_t = u_t W_Iw H ** -0.5 D ** -0.5``;
  ``I_ts = sum_h w_th ReLU(qI_th . kI_s)`` for ``s <= t``; ``S_t`` the ``min(k,
  t + 1)`` positions of largest ``I_ts``, ties to the lower position
  (``lax.top_k``'s order).  The selection is of tokens, per query, shared by
  every head.
- **Expert layer**, for normed ``u``: ``sigma = sigmoid(u W_r)`` over all
  the published ``n_routed_experts`` in float32 (``scoring_func``); the
  ``num_experts_per_tok`` of largest ``sigma + b`` chosen (``b`` the
  selection bias; ``topk_method`` ``noaux_tc`` with ``n_group`` 1, no group
  limit; ties to the lower id); weight ``w_e = routed_scaling_factor *
  sigma_e / sum sigma`` over the chosen (``norm_topk_prob``); ``y =
  SwiGLU_shared(u) + sum_e w_e SwiGLU_e(u)``.  No auxiliary loss when
  serving.  The multi-token-prediction layer is not part of the next
  token's logits and is left out (the configuration's ``reduced``).

Departures, all stated under the configuration's ``assumed``: the
published serving stack keeps the index keys and products in fp8 with a
Hadamard rotation of ``qI`` and ``kI`` (orthogonal: it cancels in ``qI .
kI`` and exists only for fp8); this configuration states bfloat16 for them
and float32 accumulation, scores and selection.  The index key's LayerNorm
has ``eps`` 1e-6 (V3.2's).  Linear weights are stored ``[in, out]``;
``kv_b_proj`` as its two halves per head; an expert's gate and up matrices
side by side (gate columns first), as are the dense layer's and the shared
expert's.

**The chip's share**, as ``deepseek_v2.py`` has it: ``n_routed_experts`` in
the configuration file counts the experts held, ``vocab_size`` the rows of
the vocabulary held, ``share.layers`` the published layers held (by their
own number: the leading dense layers count once); the reference routes
over all the published experts and adds the held experts' outputs only.
:func:`expert_layer` takes the share as an argument, so that a test can add
the shares up.

The weights are seeded and rounded to bfloat16; a layer at a time is
computed in float32, the indexer ``ROW_BLOCK`` query rows at a time and
attention a block of heads and a block of rows at a time, so that the
model at its published widths and a sequence of forty thousand tokens fit
one chip.  ``quant`` turns the reference into its own low-precision
control: the operands of every layer GEMM and of the index products rounded
to fp8; ``None`` is the reference itself.

**Near ties.**  The router's cut is followed as ``deepseek_v2.py`` follows
it (``routing``: the program's choices taken, their margin reported).  The
indexer's cut is not followed (a sequence's every query, in every layer,
would have to be handed over): the reference selects for itself, and
``index`` (:func:`hidden_states`) records the scores of the queries a
comparison names, so that :func:`selection_margin` can hold the program's
selection there to a margin.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256          # query rows of attention and of the indexer at once
HEAD_BLOCK = 8           # heads whose keys and values are expanded at once
INDEX_HEAD_BLOCK = 8     # index heads whose scores are formed at once
SEQ_BUCKET = 1024        # a sequence is padded to a multiple of this
# the query rows of ``[e - COL_BUCKET, e)`` read the keys of ``[0, e)`` only:
# causal, so a long sequence costs about half of its square
COL_BUCKET = 4096
INDEX_EPS = 1e-6

FAULTS = ("indexer_left_out", "selection_from_other_layer",
          "index_rope_left_out", "index_norm_left_out",
          "index_weights_unscaled", "routing_unnormalised", "index_keys_fp8")


def sizes_of(config: dict) -> dict:
    """The reference's sizes from the configuration file."""
    if (config["scoring_func"] != "sigmoid"
            or config["topk_method"] != "noaux_tc"
            or config["n_group"] != 1 or config["topk_group"] != 1
            or not config["norm_topk_prob"] or config["attention_bias"]
            or config["hidden_act"] != "silu" or config["moe_layer_freq"] != 1
            or config["tie_word_embeddings"]
            or config["rope_parameters"]["rope_type"] != "default"
            or not config["rope_interleave"]
            or not config["indexer_rope_interleave"]
            or config["num_nextn_predict_layers"]):
        raise ValueError("the reference does not follow this configuration")
    layers = config["num_hidden_layers"]
    share = config["share"]
    # the published layers this chip's share holds, by their own number
    held_layers = share.get("layers", list(range(layers)))
    if len(held_layers) != layers:
        raise ValueError("share.layers does not list num_hidden_layers")
    kind = {"heads": config["num_attention_heads"], "kv_heads": 1,
            "k_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            "v_dim": config["v_head_dim"], "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"],
            "q_rank": config["q_lora_rank"],
            "kv_rank": config["kv_lora_rank"],
            "theta": float(config["rope_parameters"]["rope_theta"]),
            "window": None, "sink": False,
            "index_heads": config["index_n_heads"],
            "index_dim": config["index_head_dim"],
            "index_rope": config["qk_rope_head_dim"],
            "index_topk": config["index_topk"]}
    if kind["k_dim"] != config["qk_head_dim"]:
        raise ValueError("qk_head_dim is not nope + rope")
    return {
        "hidden": config["hidden_size"], "layers": layers,
        "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
        "kinds": (kind,), "pattern": (0,) * layers,
        "experts": tuple(int(layer >= config["first_k_dense_replace"])
                         for layer in held_layers),
        "dense_ffn": config["intermediate_size"],
        "expert_ffn": config["moe_intermediate_size"],
        "n_experts": config["source_values"]["n_routed_experts"],
        "held": (share["experts_first"], config["n_routed_experts"]),
        "top_k": config["num_experts_per_tok"],
        "route_scale": float(config["routed_scaling_factor"]),
        "shared": config["n_shared_experts"],
        "init": dict(config["assumed"]["init"]),
    }


def _attention_params(sz):
    k, h = sz["kinds"][0], sz["hidden"]
    return (h * k["q_rank"] + k["q_rank"] * k["heads"] * k["k_dim"]
            + h * (k["kv_rank"] + k["rope"])
            + k["kv_rank"] * k["heads"] * (k["nope"] + k["v_dim"])
            + k["heads"] * k["v_dim"] * h)


def _index_params(sz):
    k, h = sz["kinds"][0], sz["hidden"]
    return (k["q_rank"] * k["index_heads"] * k["index_dim"]
            + h * k["index_dim"] + h * k["index_heads"])


def count_params(sz: dict, positions: bool = False) -> int:
    """Parameters one token's matrix multiplications meet on this chip:
    attention and its indexer, the dense layer, the routers, the shared
    expert, the head, and of each expert layer the share ``top_k * held /
    n_experts`` of one expert that a token expects to find here."""
    del positions
    h = sz["hidden"]
    expert = 3 * h * sz["expert_ffn"]
    n = sz["vocab"] * h
    for moe in sz["experts"]:
        n += _attention_params(sz) + _index_params(sz)
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
        n += (_attention_params(sz) + _index_params(sz) + 2 * h
              + k["q_rank"] + k["kv_rank"] + 2 * k["index_dim"])
        if moe:
            n += (h * sz["n_experts"] + sz["n_experts"]
                  + (sz["shared"] + sz["held"][1]) * expert)
        else:
            n += 3 * h * sz["dense_ffn"]
    return n


# ------------------------------------------------------------------ weights


def layer_shapes(sz: dict, layer: int) -> dict:
    """``{name: (shape, std, mean)}`` of one layer's weights.  Matrices are
    N(0, std); those that face the residual stream are scaled by ``1 /
    sqrt(2 L)``, the held experts' by ``expert_gain`` more (this chip adds a
    sixteenth of the routed experts: at the plain scale their part would
    hide under the shared expert's).  Norm gains are 1 + N(0, norm_std), the
    index key's LayerNorm bias N(0, norm_std) and the selection bias N(0,
    bias_std): a checkpoint's are not nought, and nought ones would hide a
    dropped gain or bias."""
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
        "wo": ((n * k["v_dim"], h), out_std * init["attn_gain"], 0.0),
        "wq_idx": ((k["q_rank"], k["index_heads"] * k["index_dim"]), std,
                   0.0),
        "wk_idx": ((h, k["index_dim"]), std, 0.0),
        "k_idx_norm": ((k["index_dim"],),) + gain,
        "k_idx_norm_bias": ((k["index_dim"],), init["norm_std"], 0.0),
        "w_idx": ((h, k["index_heads"]), std, 0.0),
    }
    if sz["experts"][layer]:
        f, held = sz["expert_ffn"], sz["held"][1]
        shapes.update({
            "router": ((h, sz["n_experts"]), std * init["router_gain"], 0.0),
            "router_bias": ((sz["n_experts"],), init["bias_std"], 0.0),
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
BF16 = Quant(fwd=lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))


def _operand(x, quant):
    x = x.astype(jnp.float32)
    return x if quant is None else quant.fwd(x)


def _matmul(x, w, quant):
    return jnp.dot(_operand(x, quant), _operand(w, quant), precision=HIGHEST)


# ---------------------------------------------------------------- the layers


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain.astype(jnp.float32)


def layer_norm(x, gain, bias, eps=INDEX_EPS):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)
            + bias.astype(jnp.float32))


def rotate(x, positions, theta):
    """RoPE of base ``theta`` on all of ``x [s, heads, d]``: channel ``2
    t`` pairs with ``2 t + 1`` and both stay where they lie."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def indexer(x, c_q, lw, sz, quant=None, fault=None):
    """The indexer's ``(q [s, H, D], w [s, H], k [s, D])`` for normed ``x
    [s, hidden]`` and the normed compressed query ``c_q [s, q_rank]``."""
    k = sz["kinds"][0]
    s, r = x.shape[0], k["index_rope"]
    positions = jnp.arange(s)
    q = _matmul(c_q, lw["wq_idx"], quant).reshape(
        s, k["index_heads"], k["index_dim"])
    key = _matmul(x, lw["wk_idx"], quant)
    if fault != "index_norm_left_out":
        key = layer_norm(key, lw["k_idx_norm"], lw["k_idx_norm_bias"])
    key = key[:, None, :]
    if fault != "index_rope_left_out":
        q = jnp.concatenate([rotate(q[..., :r], positions, k["theta"]),
                             q[..., r:]], -1)
        key = jnp.concatenate([rotate(key[..., :r], positions, k["theta"]),
                               key[..., r:]], -1)
    w = _matmul(x, lw["w_idx"], quant)
    if fault != "index_weights_unscaled":
        w = w * (k["index_heads"] ** -0.5 * k["index_dim"] ** -0.5)
    key = key[:, 0]
    if fault == "index_keys_fp8":
        key = FP8.fwd(key)
    return _operand(q, quant), w, _operand(key, quant)


def _index_rows(q, w, key, start, rows):
    """``I`` of the query rows ``start + [0, rows)`` against every key,
    ``-inf`` past each row's own position: ``[rows, s]``."""
    s = key.shape[0]
    qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
    wb = jax.lax.dynamic_slice_in_dim(w, start, rows, 0)
    heads = q.shape[1]
    hb = INDEX_HEAD_BLOCK if heads % INDEX_HEAD_BLOCK == 0 else heads

    def some_heads(total, h0):
        qh = jax.lax.dynamic_slice_in_dim(qb, h0, hb, 1)
        wh = jax.lax.dynamic_slice_in_dim(wb, h0, hb, 1)
        dots = jnp.einsum("rhd,sd->rhs", qh, key, precision=HIGHEST)
        # the heads' weighted sum in float32 on the vector unit: as a
        # matrix product it would be split into bfloat16 passes
        return total + jnp.sum(jnp.maximum(dots, 0.0) * wh[..., None],
                               1), None

    total, _ = jax.lax.scan(some_heads, jnp.zeros((rows, s), jnp.float32),
                            jnp.arange(0, heads, hb))
    i = start + jnp.arange(rows)[:, None]
    return jnp.where(jnp.arange(s)[None, :] <= i, total, -jnp.inf)


def _buckets(s):
    """``(first row, end)`` of each bucket of query rows: its rows read the
    keys of ``[0, end)``."""
    return [(e0, min(s, e0 + COL_BUCKET)) for e0 in range(0, s, COL_BUCKET)]


def top_k_mask(scores, k):
    """The positions ``lax.top_k(scores, k)`` names in each row of
    ``scores [rows, s]``, as a mask: every score above the row's ``k``-th
    and, of those equal to it, the ones at or before the last that top_k
    took (its ties go to the lower position)."""
    values, positions = jax.lax.top_k(scores, k)
    cut = values[:, -1:]
    last_tie = jnp.max(jnp.where(values == cut, positions, -1), -1,
                       keepdims=True)
    column = jnp.arange(scores.shape[1])[None, :]
    return (scores > cut) | ((scores == cut) & (column <= last_tie))


def select(q, w, key, topk):
    """Each query's selection, one mask ``[e1 - e0, e1]`` a bucket of
    query rows (:func:`_buckets`): the ``min(topk, e1)`` positions of
    largest score (a row selects positions past its own only where it has
    fewer live ones than that; whoever reads the mask masks them)."""
    s = key.shape[0]
    n = min(topk, s)
    out = []
    for e0, e1 in _buckets(s):
        def block(start, e1=e1):
            scores = _index_rows(q, w, key[:e1], start, ROW_BLOCK)
            return top_k_mask(scores, min(n, e1))

        out.append(jax.lax.map(block, jnp.arange(e0, e1, ROW_BLOCK)).reshape(
            e1 - e0, e1))
    return out


def attention(x, lw, sz, quant=None, fault=None, chosen=None):
    """One sparse latent attention layer on ``x [s, hidden]`` (already
    normed), in the expanded form, every query over the rows its indexer
    selected.  ``chosen`` (a fault: another layer's selection) takes the
    place of the layer's own.  Returns ``(out [s, hidden], selection (the
    masks of :func:`select`), (q, w, key))``, the last the indexer's
    rows."""
    k = sz["kinds"][0]
    s, n = x.shape[0], k["heads"]
    nope, rope, rank, dv = k["nope"], k["rope"], k["kv_rank"], k["v_dim"]
    positions = jnp.arange(s)
    scale = k["k_dim"] ** -0.5

    c_q = rms_norm(_matmul(x, lw["wq_a"], quant), lw["q_a_norm"], sz["eps"])
    index = indexer(x, c_q, lw, sz, quant, fault)
    selection = select(*index, k["index_topk"]) if chosen is None else chosen
    c_q = _operand(c_q, quant)
    c_kv = _matmul(x, lw["wkv_a"], quant)
    c = _operand(rms_norm(c_kv[:, :rank], lw["kv_a_norm"], sz["eps"]), quant)
    k_rope = rotate(c_kv[:, None, rank:], positions, k["theta"])[:, 0]
    blocks = n // HEAD_BLOCK if n % HEAD_BLOCK == 0 else 1
    hb = n // blocks
    wq_b = _operand(lw["wq_b"], quant).reshape(-1, blocks, hb * k["k_dim"])
    w_uk = _operand(lw["w_uk"], quant).reshape(blocks, hb, rank, nope)
    w_uv = _operand(lw["w_uv"], quant).reshape(blocks, hb, rank, dv)
    wo = _operand(lw["wo"], quant).reshape(blocks, hb * dv, -1)
    dense = fault == "indexer_left_out"
    masks = selection if not dense else [None] * len(_buckets(s))

    def heads(total, block):
        """The output projection of a block of heads' context, added up:
        the whole context (``s x heads x v_dim``) is never held."""
        wq, uk, uv, wo_b = block
        q = jnp.dot(c_q, wq, precision=HIGHEST).reshape(s, hb, k["k_dim"])
        q_rope = rotate(q[..., nope:], positions, k["theta"])
        k_nope = jnp.einsum("sc,ncd->snd", c, uk, precision=HIGHEST)
        v = jnp.einsum("sc,ncd->snd", c, uv, precision=HIGHEST)

        # one product for both halves of a score: the rotary key beside
        # each head's own
        qk = jnp.concatenate([q[..., :nope], q_rope], -1)
        keys = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, None], (s, hb, rope))], -1)

        def rows(start, e0, e1, picked):
            i = start + jnp.arange(ROW_BLOCK)[:, None]
            qb = jax.lax.dynamic_slice_in_dim(qk, start, ROW_BLOCK, 0)
            a = jnp.einsum("qnd,knd->nqk", qb, keys[:e1], precision=HIGHEST)
            live = positions[None, :e1] <= i
            if not dense:
                live = live & jax.lax.dynamic_slice_in_dim(
                    picked, start - e0, ROW_BLOCK, 0)
            a = jnp.where(live[None], a * scale, -jnp.inf)
            p = jax.nn.softmax(a, axis=-1)
            return jnp.einsum("nqk,knd->qnd", p, v[:e1], precision=HIGHEST)

        ctx = jnp.concatenate([
            jax.lax.map(functools.partial(rows, e0=e0, e1=e1, picked=picked),
                        jnp.arange(e0, e1, ROW_BLOCK)).reshape(
                            e1 - e0, hb * dv)
            for (e0, e1), picked in zip(_buckets(s), masks)])
        return total + jnp.dot(_operand(ctx, quant), wo_b,
                               precision=HIGHEST), None

    out, _ = jax.lax.scan(heads, jnp.zeros((s, wo.shape[-1]), jnp.float32),
                          (jnp.moveaxis(wq_b, 1, 0), w_uk, w_uv, wo))
    return out, selection, index


def swiglu(x, gate_up, down, quant=None):
    f = down.shape[0]
    gu = _matmul(x, gate_up, quant)
    return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, quant)


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale",
                                             "fault"))
def route(x, router, router_bias, top_k, route_scale, fault=None,
          chosen=None):
    """``(experts [s, k], weights [s, k], own [s, k], margin [s])`` of
    normed ``x [s, hidden]``: float32 logits over all the experts, sigmoid
    scores, the ``top_k`` largest ``score + bias`` (ties to the lower id),
    weights ``route_scale`` times the chosen scores over their sum.
    ``chosen`` and ``margin`` as ``mimo_v2_flash.route`` has them: a
    program's choice followed, and how far its lowest ``score + bias`` lies
    under the reference's own cut."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    chosen_by = scores + router_bias.astype(jnp.float32)
    own = jnp.argsort(-chosen_by, axis=-1, stable=True)[:, :top_k]
    experts = own
    if chosen is not None:
        experts = jnp.where(chosen[:, :1] >= 0, chosen, own)
    cut = jnp.take_along_axis(chosen_by, own[:, -1:], axis=-1)[:, 0]
    margin = cut - jnp.min(
        jnp.take_along_axis(chosen_by, experts, axis=-1), -1)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    if fault != "routing_unnormalised":
        picked = picked / jnp.sum(picked, -1, keepdims=True)
    return experts, route_scale * picked, own, jnp.maximum(margin, 0.0)


@functools.partial(jax.jit, static_argnames=("first", "cap", "quant"))
def _held_experts(x, experts, weights, gate_up, down, first, cap, quant):
    """Sum over the held experts of weight times expert output, each expert
    over the at most ``cap`` rows of ``x [s, h]`` routed to it."""
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


# rows of a dense or shared SwiGLU computed at once: ``[rows, 2 f]`` float32
# of a dense layer is 0.4 GB here, of a 45 k sequence 4.4 GB
FFN_ROWS = 1024


@functools.partial(jax.jit, static_argnames=("quant",))
def _swiglu(x, gate_up, down, quant=None):
    """:func:`swiglu` ``FFN_ROWS`` rows of ``x`` at a time (each row's
    result is its own: the same numbers)."""
    s = x.shape[0]
    if s <= FFN_ROWS or s % FFN_ROWS:
        return swiglu(x, gate_up, down, quant)
    out = jax.lax.map(lambda rows: swiglu(rows, gate_up, down, quant),
                      x.reshape(s // FFN_ROWS, FFN_ROWS, -1))
    return out.reshape(s, -1)


def expert_layer(x, lw, sz, held=None, quant=None, fault=None, chosen=None,
                 log=None, shared=True):
    """The share ``held = (first, count)`` of an expert feed-forward on
    normed ``x [s, hidden]``, as ``deepseek_v2.expert_layer`` has it:
    routed over all the experts, the held ones' weighted outputs added,
    and, with ``shared``, the shared expert's SwiGLU (a test that adds the
    shares up counts it once).  ``chosen`` as in :func:`route`; ``log``, a
    list, is given the layer's ``(own choices [s, k], margin [s])``."""
    first, count = sz["held"] if held is None else held
    s = x.shape[0]
    experts, weights, own, margin = route(
        x, lw["router"], lw["router_bias"], sz["top_k"], sz["route_scale"],
        fault, chosen)
    if log is not None:
        log.append((np.asarray(own), np.asarray(margin)))
    local = np.asarray(experts) - first
    busiest = max(np.bincount(local[(local >= 0) & (local < count)],
                              minlength=1))
    cap = next(c for c in (max(s // 8, 1), max(s // 2, 1), s)
               if c >= busiest)
    y = _held_experts(x, experts, weights, lw["experts_gate_up"],
                      lw["experts_down"], first, cap, quant)
    if shared:
        y = y + _swiglu(x, lw["shared_gate_up"], lw["shared_down"], quant)
    return y


def _mask_row(masks, t, s):
    """Row ``t`` of the masks of :func:`select`, ``[s]``."""
    row = jnp.zeros((s,), bool)
    for (e0, e1), mask in zip(_buckets(s), masks):
        own = jax.lax.dynamic_index_in_dim(
            mask, jnp.clip(t - e0, 0, e1 - e0 - 1), 0, keepdims=False)
        row = jnp.where((e0 <= t) & (t < e1), jnp.pad(own, (0, s - e1)), row)
    return row


ATTENTION = ("norm1", "norm2", "wq_a", "q_a_norm", "wq_b", "wkv_a",
             "kv_a_norm", "w_uk", "w_uv", "wo", "wq_idx", "wk_idx",
             "k_idx_norm", "k_idx_norm_bias", "w_idx")


@functools.partial(jax.jit, static_argnames=("sz_items", "quant", "fault"))
def _attention_block(x, lw, sz_items, quant, fault, chosen=None, at=None):
    """The attention half of a layer; where ``at`` names query positions,
    also their index scores ``[n, s]`` and their selections."""
    sz = dict(sz_items)
    sz["kinds"] = (dict(sz["kinds"]),)
    out, selection, index = attention(rms_norm(x, lw["norm1"], sz["eps"]),
                                      lw, sz, quant, fault, chosen)
    h = x + out
    seen = None
    if at is not None:
        n = x.shape[0]
        topk = min(sz["kinds"][0]["index_topk"], n)
        seen = (jax.vmap(lambda t: _index_rows(*index, t, 1)[0])(at),
                jax.vmap(lambda t: jnp.nonzero(
                    _mask_row(selection, t, n), size=topk,
                    fill_value=n)[0])(at))
    if fault != "selection_from_other_layer":
        selection = None      # no later layer reads it
    return h, rms_norm(h, lw["norm2"], sz["eps"]), selection, seen


def hidden_states(w, tokens, sz, quant=None, fault=None, routing=None,
                  index=None):
    """Final-normed hidden states ``[s, hidden]`` of one sequence ``tokens
    [s]`` (``s`` a multiple of ``ROW_BLOCK``: :func:`_padded` gives one;
    causal, so padding behind changes nothing).

    ``routing``, a dict, is the record of the expert layers' choices, in
    and out, as ``deepseek_v2.hidden_states`` has it.  ``index``, a dict,
    is the record of the indexers at the query positions ``index["at"]``:
    out, ``index["scores"] [layers, n, s]`` (numpy, ``-inf`` past each
    query) and ``index["selected"] [layers, n, k]`` (each query's own
    selection, positions past the query not tokens)."""
    small = tuple(sorted(
        [("eps", sz["eps"]),
         ("kinds", tuple(sorted(sz["kinds"][0].items())))]))
    chosen = None if routing is None else routing.get("chosen")
    if chosen is not None:
        chosen = np.asarray(chosen, np.int32)
        chosen = np.concatenate(
            [chosen, np.full((chosen.shape[0], len(tokens) - chosen.shape[1],
                              chosen.shape[2]), -1, np.int32)], 1)
    log = None if routing is None else []
    at = None if index is None else jnp.asarray(index["at"], jnp.int32)
    scores, selected = [], []
    previous = None
    x = w["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    for layer, lw in enumerate(w["layers"]):
        other = (previous if fault == "selection_from_other_layer"
                 else None)
        h, normed, selection, seen = _attention_block(
            x, {k: lw[k] for k in ATTENTION}, small, quant, fault, other, at)
        previous = selection if fault == "selection_from_other_layer" \
            else None
        if seen is not None:
            scores.append(np.asarray(seen[0]))
            selected.append(np.asarray(seen[1]))
        if sz["experts"][layer]:
            x = h + expert_layer(
                normed, lw, sz, quant=quant, fault=fault, log=log,
                chosen=None if chosen is None else chosen[len(log)])
        else:
            x = h + _swiglu(normed, lw["ffn_gate_up"], lw["ffn_down"], quant)
        # off the device before the next layer's programs load: at forty
        # thousand tokens the two are 2 GB
        del h, normed
    if routing is not None:
        routing["own"] = np.stack([own for own, _ in log])
        routing["margin"] = max(
            [0.0] + [float(np.max(m)) for _, m in log if chosen is not None])
    if index is not None:
        index["scores"] = np.stack(scores)
        index["selected"] = np.stack(selected)
    return jax.jit(rms_norm, static_argnums=2)(x, w["final_norm"], sz["eps"])


def selection_margin(scores, program, topk: int) -> float:
    """How far the program's selection ``program [k]`` (positions, ``-1``
    where none) of one query strays from the reference's own, given the
    reference's scores ``scores [s]`` of that query (``-inf`` past it):
    the largest distance from the reference's ``topk``-th score of a
    position only one of the two selected, in units of the spread of the
    query's scores (nought where the sets are one, ``inf`` where the
    program selected a position past the query or left one out where
    every position is selected)."""
    scores = np.asarray(scores, np.float64)
    live = np.isfinite(scores)
    mine = {int(p) for p in program if p >= 0}
    if any(not live[p] for p in mine if p < len(scores)) or any(
            p >= len(scores) for p in mine):
        return float("inf")
    n_live = int(live.sum())
    if n_live <= topk:
        return 0.0 if mine == set(np.nonzero(live)[0].tolist()) \
            else float("inf")
    order = np.lexsort((np.arange(len(scores)), -scores))
    ref = set(order[:topk].tolist())
    cut = scores[order[topk - 1]]
    spread = float(np.std(scores[live])) or 1.0
    stray = mine ^ ref
    if not stray:
        return 0.0
    return float(max(abs(scores[p] - cut) for p in stray) / spread)


def _padded(tokens, pad=None):
    """``tokens`` padded behind to ``pad`` where given, else to a multiple
    of ``SEQ_BUCKET`` (short ones to a multiple of ``ROW_BLOCK``)."""
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
                  routing=None, index=None):
    """Logits ``[len(served), vocab]`` at the positions that produced
    ``served`` after ``prompt``, the served tokens fed back; ``routing``
    and ``index`` as in :func:`hidden_states`."""
    n = len(prompt) + len(served) - 1
    seq = np.concatenate([prompt, served])[:n]
    hidden = hidden_states(w, _padded(seq, pad), sz, quant, fault, routing,
                           index)
    return _head(hidden[len(prompt) - 1: n], w["head"])


def last_logits(w, sequences, sz, pad=None, quant=None, fault=None,
                routing=None, index=None):
    """Logits ``[n, vocab]`` that follow each of ``sequences``: one forward
    pass each, every sequence padded to ``pad`` where given.  ``routing``:
    one record per sequence, as in :func:`hidden_states`; ``index``, where
    given, one dict per sequence, whose ``at`` defaults to the sequence's
    last position (the query that made the logits)."""
    routing = [None] * len(sequences) if routing is None else routing
    index = [None] * len(sequences) if index is None else index
    rows = []
    for seq, r, ix in zip(sequences, routing, index):
        if ix is not None:
            ix.setdefault("at", [len(seq) - 1])
        rows.append(hidden_states(w, _padded(seq, pad), sz, quant, fault, r,
                                  ix)[len(seq) - 1])
    return _head(jnp.stack(rows), w["head"])
