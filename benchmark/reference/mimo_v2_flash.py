"""Plain reference of MiMo-V2-Flash's language model: the forward pass in
straightforward float32 ``jax.numpy``, no cache, no kernels, no batching,
and nothing of ``apex_tpu``.  Every matrix multiplication runs at ``highest``
precision (on a TPU a float32 matmul is otherwise done in bfloat16 passes).

The layer equations, from the model's public ``config.json`` (the keys named
in brackets) and the catalog's ``described_as``:

- Pre-norm residual block ``h = x + Attn_l(RMSNorm(x))``, ``y = h +
  FFN_l(RMSNorm(h))``; RMSNorm with ``layernorm_epsilon`` and float32
  statistics; a final RMSNorm and an untied head.  No biases
  (``attention_bias`` false).
- Attention, kind from ``hybrid_layer_pattern[l]``: ``0`` full
  (``num_attention_heads`` query heads, ``num_key_value_heads`` KV heads,
  q/k width ``head_dim``, v width ``v_head_dim``, ``rope_theta``, no sink),
  ``1`` sliding window (the ``swa_`` keys, ``swa_rope_theta``,
  ``sliding_window``: position ``i`` sees keys ``i - window < j <= i``; a
  learned sink logit per head joins the softmax denominator and takes no
  value).  ``q = x W_q``, ``k = x W_k``, ``v = attention_value_scale * x
  W_v``; rotary on the first ``int(head_dim * partial_rotary_factor)``
  channels of each q and k head; scores ``q k^T / sqrt(head_dim)``.
- Feed-forward, kind from ``moe_layer_freq[l]``: ``0`` a dense SwiGLU of
  width ``intermediate_size``; ``1`` router logits in float32 over all the
  published ``n_routed_experts``, scores ``sigmoid``, the
  ``num_experts_per_tok`` experts with the largest ``score + bias`` (the bias
  selects and does not weigh; ``n_group = topk_group = 1``: no group limit),
  weights ``score / sum(scores of the chosen)`` (``norm_topk_prob``), each
  expert a SwiGLU of width ``moe_intermediate_size``.  No shared expert.

Read from the configuration and stated under its ``assumed``: the value
scale multiplies ``v`` (not the scores); rotary pairs channel ``t`` with
``t + rotary/2`` (half rotation); the window counts the token itself.  The
three multi-token-prediction layers ``described_as`` names have no key in
``config`` and are left out.

Departures from the checkpoint's layout, none in the arithmetic: linear
weights are stored ``[in, out]``; an expert's gate and up matrices lie side
by side (``[hidden, 2 f]``, gate columns first), as do the dense layer's.

**The chip's share.**  The configuration file holds one chip's share of a
stated deployment: ``n_routed_experts`` there counts the experts *held*
(``share.experts_first`` on), ``vocab_size`` the rows of the vocabulary
held.  The reference is given the same share: it routes over all the
published experts and adds the held experts' outputs only; what the absent
experts would add is left out, and that partial result goes on to the next
layer.  :func:`expert_layer` takes the share as an argument, so that a test
can add the shares up against the uncut layer.

The weights are seeded, held at the values the configuration states (rounded
to bfloat16); a layer at a time is computed in float32, attention rows in
blocks and each expert over the rows routed to it, so that the model at its
published widths and a sequence of six thousand tokens fit one chip.
``quant`` turns the reference into its own low-precision control: the
operands of every layer GEMM (the four attention projections, the dense
layer's and the experts' matrices) rounded to fp8; ``None`` is the
reference itself.

**Near ties in the router.**  The eighth and ninth of 256 scores lie
closer together than bfloat16 rounds a hidden state, so a sound program
and this reference choose another expert for a few tokens in a hundred,
and such a token's row then differs by an expert's output.  A comparison
may hand the reference the program's choices (``routing``,
:func:`hidden_states`): it takes them in place of its own, weighs them by
its own scores, and reports by how much the program's choice lay under its
own cut (``margin``), which the comparison holds to a limit: a choice that
is no near tie is a fault, and is not followed silently.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256          # query rows of attention computed at once
SEQ_BUCKET = 2048        # a sequence is padded to a multiple of this


def _kind(config, prefix, theta, sink_key):
    return {"heads": config[prefix + "num_attention_heads"],
            "kv_heads": config[prefix + "num_key_value_heads"],
            "k_dim": config[prefix + "head_dim"],
            "v_dim": config[prefix + "v_head_dim"],
            "theta": float(config[theta]),
            "window": config["sliding_window"] if prefix else None,
            "sink": bool(config[sink_key])}


def sizes_of(config: dict) -> dict:
    """The reference's sizes from the configuration file."""
    if (config["n_shared_experts"] or config["routed_scaling_factor"]
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["scoring_func"] != "sigmoid"
            or config["topk_method"] != "noaux_tc"
            or not config["norm_topk_prob"] or config["attention_bias"]
            or config["hidden_act"] != "silu"
            or config["tie_word_embeddings"]):
        raise ValueError("the reference does not follow this configuration")
    layers = config["num_hidden_layers"]
    pattern = tuple(config["hybrid_layer_pattern"])
    experts = tuple(config["moe_layer_freq"])
    if len(pattern) != layers or len(experts) != layers:
        raise ValueError("the layer patterns do not cover the layers")
    kinds = (_kind(config, "", "rope_theta", "add_full_attention_sink_bias"),
             _kind(config, "swa_", "swa_rope_theta",
                   "add_swa_attention_sink_bias"))
    share = config["share"]
    return {
        "hidden": config["hidden_size"], "layers": layers,
        "vocab": config["vocab_size"], "eps": config["layernorm_epsilon"],
        "kinds": kinds, "pattern": pattern, "experts": experts,
        "dense_ffn": config["intermediate_size"],
        "expert_ffn": config["moe_intermediate_size"],
        "n_experts": config["source_values"]["n_routed_experts"],
        "held": (share["experts_first"], config["n_routed_experts"]),
        "top_k": config["num_experts_per_tok"],
        "value_scale": config["attention_value_scale"],
        "rotary": int(config["head_dim"] * config["partial_rotary_factor"])
        // 2 * 2,
        "init": dict(config["assumed"]["init"]),
    }


def _attention_params(sz, kind):
    h = sz["hidden"]
    return h * (kind["heads"] * kind["k_dim"]
                + kind["kv_heads"] * (kind["k_dim"] + kind["v_dim"])) \
        + kind["heads"] * kind["v_dim"] * h


def count_params(sz: dict, positions: bool = False) -> int:
    """Parameters one token's matrix multiplications meet on this chip:
    attention, the dense layer, the routers, the head, and of each expert
    layer the share ``top_k * held / n_experts`` of one expert that a token
    expects to find here.  The token table is a gather and is not counted;
    the model has no position table."""
    del positions
    h = sz["hidden"]
    expert = 3 * h * sz["expert_ffn"]
    n = sz["vocab"] * h
    for kind, moe in zip(sz["pattern"], sz["experts"]):
        n += _attention_params(sz, sz["kinds"][kind])
        if moe:
            n += h * sz["n_experts"] + round(
                expert * sz["top_k"] * sz["held"][1] / sz["n_experts"])
        else:
            n += 3 * h * sz["dense_ffn"]
    return n


def stored_params(sz: dict) -> int:
    """Parameters the chip holds: every held expert whole, both tables."""
    h = sz["hidden"]
    n = 2 * sz["vocab"] * h + h
    for kind, moe in zip(sz["pattern"], sz["experts"]):
        n += _attention_params(sz, sz["kinds"][kind]) + 2 * h
        n += sz["kinds"][kind]["heads"] if sz["kinds"][kind]["sink"] else 0
        if moe:
            n += (h * sz["n_experts"] + sz["n_experts"]
                  + sz["held"][1] * 3 * h * sz["expert_ffn"])
        else:
            n += 3 * h * sz["dense_ffn"]
    return n


# ------------------------------------------------------------------ weights


def layer_shapes(sz: dict, layer: int) -> dict:
    """``{name: (shape, std, mean)}`` of one layer's weights.  Matrices are
    N(0, std); those that face the residual stream are scaled by
    ``1 / sqrt(2 L)``, the experts' by ``expert_gain`` more (this chip adds
    a sixteenth of the experts a token chose: at the plain scale a dropped
    expert would hide under the attention's output).  Norm gains are 1 +
    N(0, norm_std), the selection bias N(0, bias_std), the sinks
    N(sink_mean, sink_std): a checkpoint's are not nought, and nought ones
    would hide a dropped bias, sink or gain (a sink near nought weighs a
    third of a percent beside 128 keys' scores; ``sink_mean`` lifts it to a
    share of the softmax that shows)."""
    init = sz["init"]
    h, std = sz["hidden"], init["std"]
    out_std = std / math.sqrt(2.0 * sz["layers"])
    kind = sz["kinds"][sz["pattern"][layer]]
    n, g, dk, dv = (kind["heads"], kind["kv_heads"], kind["k_dim"],
                    kind["v_dim"])
    shapes = {
        "norm1": ((h,), init["norm_std"], 1.0),
        "norm2": ((h,), init["norm_std"], 1.0),
        "wq": ((h, n * dk), std, 0.0), "wk": ((h, g * dk), std, 0.0),
        "wv": ((h, g * dv), std, 0.0), "wo": ((n * dv, h), out_std, 0.0),
    }
    if kind["sink"]:
        shapes["sinks"] = ((n,), init["sink_std"], init.get("sink_mean", 0.0))
    if sz["experts"][layer]:
        f, held = sz["expert_ffn"], sz["held"][1]
        shapes.update({
            "router": ((h, sz["n_experts"]), std, 0.0),
            "router_bias": ((sz["n_experts"],), init["bias_std"], 0.0),
            "experts_gate_up": ((held, h, 2 * f), std, 0.0),
            "experts_down": ((held, f, h),
                             out_std * init["expert_gain"], 0.0)})
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


def _matmul(x, w, quant):
    """``x [rows, in] @ w [in, out]`` in float32 at ``highest`` precision;
    ``quant`` rounds both operands first (the control)."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant is not None:
        x, w = quant.fwd(x), quant.fwd(w)
    return jnp.dot(x, w, precision=HIGHEST)


# ---------------------------------------------------------------- the layers


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain.astype(jnp.float32)


def rotate(x, positions, rotary: int, theta: float):
    """Rotary on the first ``rotary`` channels of ``x [s, heads, d]``:
    channel ``t`` pairs with ``t + rotary / 2``; the rest pass through."""
    half = rotary // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                                / rotary))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], -1)


def attention(x, lw, kind, sz, quant=None, fault=None):
    """One attention layer on ``x [s, hidden]`` (already normed), rows of
    queries in blocks.  ``fault`` plants what a program can get wrong, for
    the tests that show the comparison catches it."""
    s = x.shape[0]
    n, g, dk, dv = (kind["heads"], kind["kv_heads"], kind["k_dim"],
                    kind["v_dim"])
    positions = jnp.arange(s)
    q = rotate(_matmul(x, lw["wq"], quant).reshape(s, n, dk), positions,
               sz["rotary"], kind["theta"])
    k = rotate(_matmul(x, lw["wk"], quant).reshape(s, g, dk), positions,
               sz["rotary"], kind["theta"])
    v = _matmul(x, lw["wv"], quant).reshape(s, g, dv)
    if fault != "value_scale_left_out":
        v = sz["value_scale"] * v
    k = jnp.repeat(k, n // g, axis=1)
    v = jnp.repeat(v, n // g, axis=1)
    window = kind["window"]
    if window is not None and fault == "window_too_wide":
        window += 1
    sinks = None
    if kind["sink"] and fault != "sink_left_out":
        sinks = lw["sinks"].astype(jnp.float32)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, ROW_BLOCK, 0)
        i = start + jnp.arange(ROW_BLOCK)[:, None]
        j = positions[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        a = jnp.einsum("qnd,knd->nqk", qb, k,
                       precision=HIGHEST) / math.sqrt(dk)
        a = jnp.where(seen[None], a, -jnp.inf)
        m = jnp.max(a, -1, keepdims=True)
        if sinks is not None:
            m = jnp.maximum(m, sinks[:, None, None])
        e = jnp.exp(a - m)
        denom = jnp.sum(e, -1, keepdims=True)
        if sinks is not None:
            denom = denom + jnp.exp(sinks[:, None, None] - m)
        return jnp.einsum("nqk,knd->qnd", e / denom, v, precision=HIGHEST)

    ctx = jax.lax.map(rows, jnp.arange(0, s, ROW_BLOCK))
    return _matmul(ctx.reshape(s, n * dv), lw["wo"], quant)


def swiglu(x, gate_up, down, quant=None):
    f = down.shape[0]
    gu = _matmul(x, gate_up, quant)
    return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, quant)


@functools.partial(jax.jit, static_argnames=("top_k", "fault"))
def route(x, router, router_bias, top_k, fault=None, chosen=None):
    """``(experts [s, k], weights [s, k], own [s, k], margin [s])`` of
    normed ``x [s, hidden]``: float32 logits over all the experts, sigmoid
    scores, the ``top_k`` largest ``score + bias`` (ties to the lower id),
    their scores normalised.

    ``chosen [s, k]``, where given, are the experts a program chose: a row
    of it that is not ``-1`` takes the place of the reference's ``own``
    choice in ``experts``, weighed by the reference's own scores.  Near the
    cut the scores lie closer together than a lower precision rounds, so a
    sound program's choice differs now and then, by a near tie, and the
    token then meets another expert: the comparison follows the program's
    choice and holds it to ``margin``, how far the lowest ``score + bias``
    among the program's choice lies under the reference's own cut (nought
    where the two sets are one)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    chosen_by = scores
    if fault != "selection_bias_left_out":
        chosen_by = scores + router_bias.astype(jnp.float32)
    own = jnp.argsort(-chosen_by, axis=-1, stable=True)[:, :top_k]
    experts = own
    if chosen is not None:
        experts = jnp.where(chosen[:, :1] >= 0, chosen, own)
    cut = jnp.take_along_axis(chosen_by, own[:, -1:], axis=-1)[:, 0]
    margin = cut - jnp.min(
        jnp.take_along_axis(chosen_by, experts, axis=-1), -1)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    return (experts, picked / jnp.sum(picked, -1, keepdims=True), own,
            margin)


@functools.partial(jax.jit, static_argnames=("first", "cap", "quant",
                                             "skip_last"))
def _held_experts(x, experts, weights, gate_up, down, first, cap, quant,
                  skip_last):
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

    count = gate_up.shape[0] - (1 if skip_last else 0)
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    return y


def expert_layer(x, lw, sz, held=None, quant=None, fault=None, chosen=None,
                 log=None):
    """The share ``held = (first, count)`` of an expert feed-forward on
    normed ``x [s, hidden]``: routed over all the experts, the held ones'
    weighted outputs added; ``lw["experts_*"]`` hold the share's experts.
    Each expert is computed over the rows routed to it: a static ``cap`` of
    them, the smallest of ``s / 8``, ``s / 2`` and ``s`` that the busiest
    held expert's rows fit (counted first, so no row is ever dropped).
    ``chosen`` as in :func:`route`; ``log``, a list, is given the layer's
    ``(own choices [s, k], margin [s])``."""
    first, count = sz["held"] if held is None else held
    s = x.shape[0]
    experts, weights, own, margin = route(
        x, lw["router"], lw["router_bias"], sz["top_k"], fault, chosen)
    if log is not None:
        log.append((np.asarray(own), np.asarray(margin)))
    local = np.asarray(experts) - first
    busiest = max(np.bincount(local[(local >= 0) & (local < count)],
                              minlength=1))
    cap = next(c for c in (max(s // 8, 1), max(s // 2, 1), s)
               if c >= busiest)
    return _held_experts(x, experts, weights, lw["experts_gate_up"],
                         lw["experts_down"], first, cap, quant,
                         fault == "held_expert_left_out")


@functools.partial(jax.jit, static_argnames=("kind_items", "sz_items",
                                             "quant", "fault"))
def _attention_block(x, lw, kind_items, sz_items, quant, fault):
    sz, kind = dict(sz_items), dict(kind_items)
    h = x + attention(rms_norm(x, lw["norm1"], sz["eps"]), lw, kind, sz,
                      quant, fault)
    return h, rms_norm(h, lw["norm2"], sz["eps"])


_dense = jax.jit(swiglu, static_argnames=("quant",))


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
    lay under this pass's own cut."""
    small = tuple(sorted((k, sz[k]) for k in ("eps", "rotary",
                                              "value_scale")))
    chosen = None if routing is None else routing.get("chosen")
    if chosen is not None:
        chosen = np.asarray(chosen, np.int32)
        chosen = np.concatenate(
            [chosen, np.full((chosen.shape[0], len(tokens) - chosen.shape[1],
                              chosen.shape[2]), -1, np.int32)], 1)
    log = None if routing is None else []
    x = w["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    for layer, lw in enumerate(w["layers"]):
        kind = sz["kinds"][sz["pattern"][layer]]
        attn = {k: lw[k] for k in ("norm1", "norm2", "wq", "wk", "wv", "wo",
                                   "sinks") if k in lw}
        h, normed = _attention_block(x, attn, tuple(sorted(kind.items())),
                                     small, quant, fault)
        if sz["experts"][layer]:
            x = h + expert_layer(
                normed, lw, sz, quant=quant, fault=fault, log=log,
                chosen=None if chosen is None else chosen[len(log)])
        else:
            x = h + _dense(normed, lw["ffn_gate_up"], lw["ffn_down"], quant)
    if routing is not None:
        routing["own"] = np.stack([own for own, _ in log])
        routing["margin"] = max(
            [0.0] + [float(np.max(m)) for _, m in log if chosen is not None])
    return jax.jit(rms_norm, static_argnums=2)(x, w["final_norm"], sz["eps"])


def _padded(tokens):
    """``tokens`` padded behind to a multiple of ``SEQ_BUCKET`` (short ones
    to a multiple of ``ROW_BLOCK``): few lengths, so few compilations."""
    step = SEQ_BUCKET if len(tokens) > SEQ_BUCKET // 2 else ROW_BLOCK
    out = np.zeros((-(-len(tokens) // step) * step,), np.int32)
    out[:len(tokens)] = tokens
    return out


@jax.jit
def _head(rows, head):
    return jnp.dot(rows, head.astype(jnp.float32), precision=HIGHEST)


def served_logits(w, prompt, served, sz, quant=None, fault=None,
                  routing=None):
    """Logits ``[len(served), vocab]`` at the positions that produced
    ``served`` after ``prompt``, the served tokens fed back."""
    n = len(prompt) + len(served) - 1
    seq = np.concatenate([prompt, served])[:n]
    hidden = hidden_states(w, _padded(seq), sz, quant, fault, routing)
    return _head(hidden[len(prompt) - 1: n], w["head"])


def served_token_gaps(w, prompt, served, sz, pad=None, routing=None):
    """For each greedy served token, how far its logit lies below the
    reference's best at its position; 0 where the reference agrees.
    ``routing`` as in :func:`hidden_states`."""
    rows = served_logits(w, prompt, served, sz, routing=routing)
    picked = jnp.take_along_axis(rows, jnp.asarray(served)[:, None], 1)[:, 0]
    return jnp.max(rows, axis=-1) - picked


def last_logits(w, sequences, sz, pad=None, quant=None, fault=None,
                routing=None):
    """Logits ``[n, vocab]`` that follow each of ``sequences`` (lists of
    token ids): one forward pass each.  ``routing``: one record per
    sequence, as in :func:`hidden_states`."""
    routing = [None] * len(sequences) if routing is None else routing
    rows = [hidden_states(w, _padded(seq), sz, quant, fault, r)[len(seq) - 1]
            for seq, r in zip(sequences, routing)]
    return _head(jnp.stack(rows), w["head"])
