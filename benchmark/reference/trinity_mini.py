"""Plain reference of Trinity-Mini's language model (``model_type: afmoe``):
forward pass, next-token loss, gradients and Adam in straightforward float32
``jax.numpy``, no kernels, and nothing of ``apex_tpu``.  Every matrix
multiplication runs at ``highest`` precision (on a TPU a float32 matmul is
otherwise done in bfloat16 passes).

The layer equations, from the model's public ``config.json`` (the keys named
in brackets) and, where the configuration file's ``assumed`` says so, from
the public ``afmoe`` modelling code::

    h0 = E[token] * sqrt(hidden_size)                      [mup_enabled]
    layer l, input h:
      a = RMSNorm_in(h)
      q = a W_q [heads x head_dim]; k = a W_k, v = a W_v [kv heads x head_dim]
      g = a W_g [heads * head_dim]
      q = RMSNorm_q(q), k = RMSNorm_k(k)     over head_dim, one gain each
      sliding layers: rotary on every channel of q and k (half rotation,
        rope_theta); full layers: no position signal      [layer_types]
      s_ij = q_i . k_j / sqrt(head_dim), j <= i; sliding layers also
        i - sliding_window < j (the token itself counts)
      o = softmax(s) v  (query head n reads KV head n // (heads / kv heads))
      o = o * sigmoid(g)
      h = h + RMSNorm_post_attn(o W_o)
      m = RMSNorm_pre_mlp(h)
      l < num_dense_layers: f = SwiGLU(m), width intermediate_size
      else: p = sigmoid(m W_r) in float32 over all the published experts
            chosen = the num_experts_per_tok largest p + expert_bias
            w = p[chosen] / (sum p[chosen] + 1e-20) * route_scale
            f = SwiGLU_shared(m) + sum over chosen e of w_e SwiGLU_e(m),
                widths moe_intermediate_size
      h = h + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(h) W_head;  loss = mean next-token cross entropy

RMSNorm with ``rms_norm_eps`` and float32 statistics; no biases; untied
head.  The rule that moves ``expert_bias`` between steps is not in
``config.json`` and is left out: the bias is a constant that receives no
gradient, and no auxiliary loss is added.

Departures from the checkpoint's layout, none in the arithmetic: linear
weights are stored ``[in, out]``; an expert's gate and up matrices lie side
by side (``[hidden, 2 f]``, gate columns first), as do the dense layer's and
the shared expert's.

**The chip's share.**  The configuration file holds one chip's share of a
stated deployment: ``num_experts`` there counts the experts *held*
(``share.experts_first`` on), ``vocab_size`` the rows of the vocabulary
held.  The reference is given the same share: it routes over all the
published experts and adds the held experts' outputs (and the shared
expert's, which every chip computes for its own tokens); what the absent
experts would add is left out.  :func:`expert_layer` takes the share as an
argument, so that a test can add the shares up against the uncut layer.

One sequence at a time, each layer recomputed in the backward pass,
attention by an explicit mask over blocks of query rows, the expert layer
as a loop over the held experts, each over every token with its weight
(nought where the token did not choose it), the loss over blocks of rows:
so 16,384 tokens a step at the published widths fit one chip beside the
float32 weights, their gradient and Adam's two moments.  ``quant`` turns
the reference into its own low-precision control: the operands of every
layer GEMM (attention's five projections, the dense layer's, the shared
expert's and the experts' matrices), forward and backward, rounded to fp8;
``None`` is the reference itself.

**Near ties in the router.**  The eighth and ninth of 128 scores lie closer
together than bfloat16 rounds a hidden state, so a sound program and this
reference choose another expert for some tokens (a third of them in some
layer under the seeded weights' peaked softmaxes, PERF.md section 6).  A
comparison hands the reference the program's choices (``chosen``): it takes
them in place of its own, weighs them by its own scores, and reports by how
much the program's choice lay under its own cut (``margin``), which the
comparison holds to a limit: a choice that is no near tie is a fault.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256          # query rows of attention computed at once
LOSS_ROWS = 1024         # rows of logits computed at once
ROUTE_EPS = 1e-20


def sizes_of(config: dict) -> dict:
    """The reference's sizes from the configuration file."""
    if (config["score_func"] != "sigmoid" or not config["route_norm"]
            or config["hidden_act"] != "silu" or config["tie_word_embeddings"]
            or config["rope_scaling"] is not None
            or not config["mup_enabled"]
            or any(config[k] != 1 for k in ("n_group", "topk_group",
                                            "num_expert_groups",
                                            "num_limited_groups"))):
        raise ValueError("the reference does not follow this configuration")
    layers = config["num_hidden_layers"]
    kinds = tuple(config["layer_types"])
    if len(kinds) != layers or set(kinds) - {"sliding_attention",
                                             "full_attention"}:
        raise ValueError("layer_types does not describe the layers")
    h = config["hidden_size"]
    return {
        "hidden": h, "layers": layers, "eps": config["rms_norm_eps"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "sliding": tuple(k == "sliding_attention" for k in kinds),
        "window": config["sliding_window"], "theta": float(
            config["rope_theta"]),
        "dense_layers": config["num_dense_layers"],
        "dense_ffn": config["intermediate_size"],
        "expert_ffn": config["moe_intermediate_size"],
        "n_experts": config["source_values"]["num_experts"],
        "held": (config["share"]["experts_first"], config["num_experts"]),
        "top_k": config["num_experts_per_tok"],
        "shared": config["num_shared_experts"],
        "route_scale": config["route_scale"],
        "embed_scale": math.sqrt(h),
        "vocab": config["vocab_size"],
        "vocab_padded": config["assumed"]["padded_vocab_size"],
        "init": tuple(sorted(config["assumed"]["init"].items())),
    }


def is_expert_layer(sz: dict, layer: int) -> bool:
    return layer >= sz["dense_layers"]


def _attention_params(sz):
    h, n, g, d = sz["hidden"], sz["heads"], sz["kv_heads"], sz["head_dim"]
    return h * d * (3 * n + 2 * g)          # W_q, W_g, W_o; W_k, W_v


def count_params(sz: dict, positions: bool = False) -> int:
    """Parameters one token's matrix multiplications meet on this chip:
    attention, the dense layer, the routers, the shared experts, the head,
    and of each expert layer the share ``top_k * held / n_experts`` of one
    expert that a token expects to find here.  The token table is a gather
    and is not counted; the model has no position table."""
    del positions
    h = sz["hidden"]
    expert = 3 * h * sz["expert_ffn"]
    n = sz["vocab_padded"] * h
    for layer in range(sz["layers"]):
        n += _attention_params(sz)
        if is_expert_layer(sz, layer):
            n += h * sz["n_experts"] + sz["shared"] * expert + round(
                expert * sz["top_k"] * sz["held"][1] / sz["n_experts"])
        else:
            n += 3 * h * sz["dense_ffn"]
    return n


def stored_params(sz: dict) -> int:
    """Parameters the chip holds: every held expert whole, both tables."""
    h = sz["hidden"]
    n = 2 * sz["vocab_padded"] * h + h
    for layer in range(sz["layers"]):
        n += _attention_params(sz) + 4 * h + 2 * sz["head_dim"]
        if is_expert_layer(sz, layer):
            n += (h * sz["n_experts"] + sz["n_experts"]
                  + (sz["held"][1] + sz["shared"]) * 3 * h * sz["expert_ffn"])
        else:
            n += 3 * h * sz["dense_ffn"]
    return n


# ------------------------------------------------------------------ weights


def layer_shapes(sz: dict, layer: int) -> dict:
    """``{name: (shape, std, mean)}`` of one layer's weights.  Matrices are
    N(0, std), those that face the residual stream scaled by ``1 / sqrt(2
    L)``; norm gains are 1 + N(0, norm_std), the QK-norm's ``qk_norm_mean``
    + N(0, norm_std), and the selection bias N(0, bias_std), centred over
    each chip's share by :func:`init_weights`: a checkpoint's are not
    nought, and nought ones would hide a dropped norm or bias.

    Why the QK-norm's gains are not about 1: at 1 a score is N(0, 1) and a
    random head averages some 750 of its 2,048 keys; the average of random
    values is next to nothing, the post-norm scales it back to unit size,
    and what it scales up is the part every position of a stretch shares.
    From the second layer on nine tenths of attention's output is that
    shared direction, a router turns it into favourite experts for whole
    stretches of positions, and the pairs a chip's share takes swing by a
    factor of two from draw to draw (PERF.md section 6).  At gains of 2 a
    score is N(0, 4), a head reads a few keys as a trained head does, its
    output is its own position's, and the routers see tokens."""
    init = dict(sz["init"])
    h, std = sz["hidden"], init["std"]
    out_std = std / math.sqrt(2.0 * sz["layers"])
    n, g, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    gain = (init["norm_std"], 1.0)
    qk_gain = (init["norm_std"], init["qk_norm_mean"])
    shapes = {
        "norm1": ((h,),) + gain, "norm2": ((h,),) + gain,
        "post_attn_norm": ((h,),) + gain, "post_ffn_norm": ((h,),) + gain,
        "q_norm": ((d,),) + qk_gain, "k_norm": ((d,),) + qk_gain,
        "wq": ((h, n * d), std, 0.0), "wk": ((h, g * d), std, 0.0),
        "wv": ((h, g * d), std, 0.0), "wg": ((h, n * d), std, 0.0),
        "wo": ((n * d, h), out_std, 0.0),
    }
    if is_expert_layer(sz, layer):
        f, held = sz["expert_ffn"], sz["held"][1]
        shapes.update({
            "router": ((h, sz["n_experts"]), std, 0.0),
            "router_bias": ((sz["n_experts"],), init["bias_std"], 0.0),
            "experts_gate_up": ((held, h, 2 * f), std, 0.0),
            "experts_down": ((held, f, h), out_std, 0.0),
            "shared_gate_up": ((h, 2 * f * sz["shared"]), std, 0.0),
            "shared_down": ((f * sz["shared"], h), out_std, 0.0)})
    else:
        f = sz["dense_ffn"]
        shapes.update({"ffn_gate_up": ((h, 2 * f), std, 0.0),
                       "ffn_down": ((f, h), out_std, 0.0)})
    return shapes


def init_weights(key, sz: dict) -> dict:
    """Seeded float32 weights: ``{"embedding" [V, h], "head" [h, V],
    "final_norm" [h], "layers": [one dict per layer]}``, ``V`` the padded
    vocabulary."""
    init = dict(sz["init"])
    v, h = sz["vocab_padded"], sz["hidden"]
    top = {"embedding": ((v, h), init["std"], 0.0),
           "head": ((h, v), init["std"], 0.0),
           "final_norm": ((h,), init["norm_std"], 1.0)}

    def leaf(key, shape, std, mean):
        return mean + std * jax.random.normal(key, shape, jnp.float32)

    w = {name: leaf(jax.random.fold_in(key, i), *spec)
         for i, (name, spec) in enumerate(sorted(top.items()))}
    w["layers"] = []
    for layer in range(sz["layers"]):
        lkey = jax.random.fold_in(key, 1000 + layer)
        lw = {name: leaf(jax.random.fold_in(lkey, i), *spec)
              for i, (name, spec) in enumerate(
                  sorted(layer_shapes(sz, layer).items()))}
        if "router_bias" in lw:
            # centred over each chip's share of the experts: the bias makes
            # the experts of a share unevenly busy and favours no share
            share = lw["router_bias"].reshape(-1, sz["held"][1])
            lw["router_bias"] = (share - jnp.mean(share, 1, keepdims=True)
                                 ).reshape(-1)
        w["layers"].append(lw)
    return w


def weight_shardings(devices, sz: dict):
    """Where the reference keeps its weights: whole, on the first chip (the
    configuration is one chip's share)."""
    from jax.sharding import SingleDeviceSharding

    shapes = jax.eval_shape(lambda k: init_weights(k, sz),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda _: SingleDeviceSharding(devices[0]), shapes)


# -------------------------------------------------------------- the control


def fp_quant(exponent_bits: int, mantissa_bits: int, max_value: float):
    """Per-tensor scaled rounding to a small float format (amax scaling, as
    fp8 recipes do), returned in float32."""
    def quant(x):
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        s = max_value / amax
        rounded = jax.lax.reduce_precision(x * s, exponent_bits,
                                           mantissa_bits) / s
        # straight through: the rounding has no gradient of its own
        return x + jax.lax.stop_gradient(rounded - x)
    return quant


# The fp8 recipe of transformer training: e4m3 for the operands of the
# forward GEMMs, e5m2 for the gradient that enters the two backward GEMMs.
Quant = collections.namedtuple("Quant", "fwd bwd")
FP8 = Quant(fwd=fp_quant(4, 3, 240.0), bwd=fp_quant(5, 2, 57344.0))


@functools.lru_cache(maxsize=None)
def _quant_matmul(quant):
    """``x [rows, in] @ w [in, out]`` with every GEMM of the forward and
    backward passes on operands rounded by ``quant``."""
    @jax.custom_vjp
    def matmul(x, w):
        return jnp.dot(quant.fwd(x), quant.fwd(w), precision=HIGHEST)

    def forward(x, w):
        xq, wq = quant.fwd(x), quant.fwd(w)
        return jnp.dot(xq, wq, precision=HIGHEST), (xq, wq)

    def backward(kept, dy):
        xq, wq = kept
        dy = quant.bwd(dy)
        return (jnp.dot(dy, wq.T, precision=HIGHEST),
                jnp.dot(xq.T, dy, precision=HIGHEST))

    matmul.defvjp(forward, backward)
    return matmul


def _matmul(x, w, quant):
    if quant is not None:
        return _quant_matmul(quant)(x, w)
    return jnp.dot(x, w, precision=HIGHEST)


# ---------------------------------------------------------------- the layers


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def rotate(x, theta: float):
    """Rotary on every channel of ``x [s, heads, d]`` at positions ``0..s``:
    channel ``t`` pairs with ``t + d / 2``."""
    s, _, d = x.shape
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(a, lw, sliding: bool, sz, quant=None):
    """One attention layer on normed ``a [s, hidden]``, rows of queries in
    blocks, before the post-norm."""
    s = a.shape[0]
    n, g, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = rms_norm(_matmul(a, lw["wq"], quant).reshape(s, n, d), lw["q_norm"],
                 sz["eps"])
    k = rms_norm(_matmul(a, lw["wk"], quant).reshape(s, g, d), lw["k_norm"],
                 sz["eps"])
    v = _matmul(a, lw["wv"], quant).reshape(s, g, d)
    if sliding:
        q, k = rotate(q, sz["theta"]), rotate(k, sz["theta"])
    k = jnp.repeat(k, n // g, axis=1)
    v = jnp.repeat(v, n // g, axis=1)
    block = math.gcd(s, ROW_BLOCK)
    # a block of query rows meets every key, or in a sliding layer the band
    # of keys its window can reach (rows before the sequence are nought and
    # masked); the mask is explicit either way
    band = sliding and sz["window"] + block < s
    reach = sz["window"] if band else 0
    if band:
        k, v = (jnp.pad(x, ((reach, 0), (0, 0), (0, 0))) for x in (k, v))

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        i = start + jnp.arange(block)[:, None]
        if band:
            kb, vb = (jax.lax.dynamic_slice_in_dim(x, start, reach + block, 0)
                      for x in (k, v))
            j = start - reach + jnp.arange(reach + block)[None, :]
        else:
            kb, vb, j = k, v, jnp.arange(s)[None, :]
        seen = (j <= i) & (j >= 0)
        if sliding:
            seen = seen & (j > i - sz["window"])
        scores = jnp.einsum("qnd,knd->nqk", qb, kb,
                            precision=HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("nqk,knd->qnd", probs, vb, precision=HIGHEST)

    ctx = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, n * d)
    ctx = ctx * jax.nn.sigmoid(_matmul(a, lw["wg"], quant))
    return _matmul(ctx, lw["wo"], quant)


def swiglu(x, gate_up, down, quant=None):
    f = down.shape[0]
    gu = _matmul(x, gate_up, quant)
    return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, quant)


def route(m, router, router_bias, top_k, route_scale, chosen=None):
    """``(experts [s, k], weights [s, k], own [s, k], margin [s])`` of
    normed ``m [s, hidden]``: float32 logits over all the experts, sigmoid
    scores, the ``top_k`` largest ``score + bias`` (ties to the lower id),
    weights their scores over their sum plus 1e-20, times ``route_scale``.
    The choice has no gradient; the weights have theirs.

    ``chosen [s, k]``, where given, are the experts a program chose: they
    take the place of the reference's ``own`` in ``experts``, weighed by
    the reference's own scores, and ``margin`` says how far the lowest
    ``score + bias`` among them lies under the reference's own cut (nought
    where the two sets are one)."""
    scores = jax.nn.sigmoid(jnp.dot(m, router, precision=HIGHEST))
    chosen_by = jax.lax.stop_gradient(scores) + router_bias
    own = jnp.argsort(-chosen_by, axis=-1, stable=True)[:, :top_k]
    experts = own if chosen is None else chosen
    cut = jnp.take_along_axis(chosen_by, own[:, -1:], axis=-1)[:, 0]
    margin = cut - jnp.min(
        jnp.take_along_axis(chosen_by, experts, axis=-1), -1)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = picked / (jnp.sum(picked, -1, keepdims=True)
                        + ROUTE_EPS) * route_scale
    return experts, weights, own, margin


def expert_layer(m, lw, sz, held=None, quant=None, chosen=None, shared=True):
    """The share ``held = (first, count)`` of an expert feed-forward on
    normed ``m [s, hidden]``, before the post-norm: routed over all the
    experts, the held ones' weighted outputs added (``lw["experts_*"]``
    hold the share's experts), and with ``shared`` the shared expert's,
    which every chip computes for its own tokens.  Returns ``(f, own
    choices [s, k], margin [s])``."""
    first, count = sz["held"] if held is None else held
    experts, weights, own, margin = route(
        m, lw["router"], lw["router_bias"], sz["top_k"], sz["route_scale"],
        chosen)

    @jax.checkpoint
    def one(f, xs):
        e, gate_up, down = xs
        w = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        return f + w[:, None] * swiglu(m, gate_up, down, quant), None

    f = (swiglu(m, lw["shared_gate_up"], lw["shared_down"], quant)
         if shared and sz["shared"] else jnp.zeros_like(m))
    f, _ = jax.lax.scan(one, f, (jnp.arange(count), lw["experts_gate_up"],
                                 lw["experts_down"]))
    return f, own, margin


def block(x, lw, layer, sz, quant=None, chosen=None):
    """One layer on the residual stream ``x [s, hidden]``; ``(x, own
    choices, margin)``, the last two ``None`` for a dense layer."""
    eps = sz["eps"]
    out = attention(rms_norm(x, lw["norm1"], eps), lw, sz["sliding"][layer],
                    sz, quant)
    x = x + rms_norm(out, lw["post_attn_norm"], eps)
    m = rms_norm(x, lw["norm2"], eps)
    own = margin = None
    if is_expert_layer(sz, layer):
        f, own, margin = expert_layer(m, lw, sz, quant=quant, chosen=chosen)
    else:
        f = swiglu(m, lw["ffn_gate_up"], lw["ffn_down"], quant)
    return x + rms_norm(f, lw["post_ffn_norm"], eps), own, margin


def hidden_states(w, tokens, sz, quant=None, chosen=None):
    """Final-normed hidden states ``[s, hidden]`` of one sequence ``tokens
    [s]``, with the expert layers' own choices ``[expert layers, s, k]``
    and the largest margin of a followed choice.  ``chosen [expert layers,
    s, k]``: a program's choices, taken in place of the reference's own."""
    x = w["embedding"][tokens] * sz["embed_scale"]
    owns, margins = [], []
    for layer, lw in enumerate(w["layers"]):
        follow = (chosen[len(owns)] if chosen is not None
                  and is_expert_layer(sz, layer) else None)
        x, own, margin = jax.checkpoint(
            functools.partial(block, layer=layer, sz=sz, quant=quant))(
                x, lw, chosen=follow)
        if own is not None:
            owns.append(own)
            margins.append(jnp.max(margin))
    k = sz["top_k"]
    return (rms_norm(x, w["final_norm"], sz["eps"]),
            jnp.stack(owns) if owns else jnp.zeros((0, len(tokens), k), int),
            jnp.max(jnp.stack(margins)) if margins else jnp.float32(0.0))


def summed_loss(w, tokens, sz, quant=None, chosen=None):
    """Sum over the positions of one sequence of the next-token cross
    entropy (position t predicts token t + 1) over the padded vocabulary,
    the logits a block of rows at a time; with the choices and margin of
    :func:`hidden_states`."""
    hidden, own, margin = hidden_states(w, tokens, sz, quant, chosen)
    hidden, targets = hidden[:-1], tokens[1:]
    n = hidden.shape[0]
    rows = min(LOSS_ROWS, n)
    pad = -n % rows
    hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
    targets = jnp.pad(targets, (0, pad))
    counted = jnp.arange(n + pad) < n

    @jax.checkpoint
    def rows_loss(total, xs):
        hid, tgt, cnt = xs
        logits = jnp.dot(hid, w["head"], precision=HIGHEST)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(cnt, logz - picked, 0.0)), None

    total, _ = jax.lax.scan(rows_loss, jnp.float32(0.0), tuple(
        x.reshape((-1, rows) + x.shape[1:])
        for x in (hidden, targets, counted)))
    return total, (own, margin)


@functools.partial(jax.jit, static_argnames=("sz_items", "quant"))
def _batch_grad(w, tokens, chosen, sz_items, quant):
    """Mean loss of ``tokens [b, s]`` with its gradient, one sequence at a
    time (their gradients add up inside the one backward pass), and the
    choices ``[b, expert layers, s, k]`` and largest margin."""
    sz = dict(sz_items)
    b, s = tokens.shape

    def mean_loss(w):
        def one(total, xs):
            loss, (own, margin) = summed_loss(
                w, xs[0], sz, quant, None if chosen is None else xs[1])
            return total + loss, (own, margin)

        total, (own, margin) = jax.lax.scan(
            one, jnp.float32(0.0),
            (tokens,) if chosen is None else (tokens, chosen))
        return total / (b * (s - 1)), (own, jnp.max(margin))

    (loss, (own, margin)), g = jax.value_and_grad(mean_loss, has_aux=True)(w)
    return loss, g, own, margin


def loss_and_grad(w, tokens, sz, quant=None, chosen=None):
    """``(mean loss, gradient, own choices [b, expert layers, s, k], largest
    margin)`` of ``tokens [b, s]``; ``chosen`` as the own choices are
    shaped."""
    return _batch_grad(w, tokens, chosen, tuple(sorted(sz.items())), quant)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def adam_step(w, g, m, v, t, lr, b1, b2, eps):
    """Adam with bias correction and no weight decay, ``t`` counted from 1;
    the old weights and moments are given up."""
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v

    out = jax.tree_util.tree_map(leaf, w, g, m, v)
    return tuple(jax.tree_util.tree_map(lambda o: o[i], out,
                                        is_leaf=lambda o: isinstance(o, tuple))
                 for i in range(3))


def compared_leaves(tree: dict, sz: dict) -> dict:
    """The leaves whose norms are compared, by name: the top-level ones,
    each layer's as ``L<layer>.<name>``, and the held experts' stacked
    matrices one expert at a time (``L<layer>.experts_down.<e>``): one
    expert's gradient left out is a thirty-second of the stack's norm and
    all of its own."""
    out = {k: x for k, x in tree.items() if k != "layers"}
    for layer, lw in enumerate(tree["layers"]):
        for name, x in lw.items():
            if name.startswith("experts_"):
                for e in range(x.shape[0]):
                    out[f"L{layer}.{name}.{e}"] = x[e]
            else:
                out[f"L{layer}.{name}"] = x
    return out


def leaf_norms(tree: dict, sz: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in compared_leaves(tree, sz).items()}


def delta_norms(a: dict, b: dict, sz: dict) -> dict:
    return leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b),
        sz)


def train(key, batches, sz, hyper, chosen=None, quant=None):
    """Follow ``len(batches)`` Adam steps from the weights of ``key`` (made
    here, and made again at the end for the change: a copy kept beside the
    weights, their gradient and the two moments would not fit the chip).
    ``chosen [steps][b, expert layers, s, k]``: a program's expert choices,
    followed.  Returns the loss of each step, the per-leaf norm of the
    first step's gradient, the per-leaf norm of the parameters' change over
    all the steps, the largest margin of a followed choice, the share of
    (token, layer) choices of the first step that differ from the
    reference's own, and the choices of this pass itself (``own [steps][b,
    expert layers, s, k]``: what a comparison follows when this pass, in
    another precision, stands in a program's place)."""
    make = jax.jit(lambda k: init_weights(k, sz))
    norms = jax.jit(lambda t: leaf_norms(t, sz))
    deltas = jax.jit(lambda w, k: delta_norms(w, init_weights(k, sz), sz))
    w = make(key)
    # Adam's two moments wait on the host while a step's gradient is made:
    # beside the weights, the gradient and the backward pass's temporaries
    # (the two sequences' gradients are two trees until they are added)
    # they would not fit the chip
    m = v = None
    losses, first, margin, flipped, owns = [], None, 0.0, 0.0, []
    for t, tokens in enumerate(batches, start=1):
        follow = None if chosen is None else jnp.asarray(chosen[t - 1])
        loss, g, own, worst = loss_and_grad(w, tokens, sz, quant, follow)
        losses.append(float(loss))
        owns.append(jax.device_get(own))
        if follow is not None:
            margin = max(margin, float(worst))
        if first is None:
            first = {k: float(x) for k, x in norms(g).items()}
            if follow is not None:
                flipped = float(jnp.mean(jnp.any(
                    jnp.sort(own, -1) != jnp.sort(follow, -1), -1)))
        if m is None:
            m = jax.tree_util.tree_map(jnp.zeros_like, g)
            v = jax.tree_util.tree_map(jnp.zeros_like, g)
        w, m, v = adam_step(w, g, jax.device_put(m), jax.device_put(v),
                            jnp.float32(t), hyper["lr"], hyper["beta1"],
                            hyper["beta2"], hyper["eps"])
        del g
        m, v = (jax.device_get((m, v)) if t < len(batches) else (None, None))
    delta = {k: float(x) for k, x in deltas(w, key).items()}
    return {"losses": losses, "grad_norms": first, "delta_norms": delta,
            "router_choice_margin": margin, "choices_flipped": flipped,
            "own": owns}
