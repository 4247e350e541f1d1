"""Plain reference of the GPT-2 family: forward pass, next-token loss,
gradients and Adam, in straightforward float32 ``jax.numpy``.

It follows the published GPT-2 description (pre-LayerNorm decoder blocks,
learned positions, causal softmax attention at 1/sqrt(d), output head tied
to the token table) and imports nothing of ``apex_tpu``.  The GeLU is the
one the configuration's ``activation_function`` names: ``gelu_new`` is
GPT-2's tanh form, ``gelu`` the exact one (erf).  Every
matrix multiplication runs at ``highest`` precision: on a TPU a float32
matmul is otherwise done in bfloat16 passes.

Departures from the Hugging Face checkpoint layout, none of them in the
arithmetic: linear weights are stored ``[out, in]``, and the fused QKV
projection is head-major (for each head its q, k and v rows), which is the
Megatron order.  With seeded random weights the orders are equivalent.

The weights are a flat dict; the per-layer ones are stacked ``[L, ...]``:

    wte [V, h]  wpe [P, h]  lnf_g lnf_b [h]
    ln1_g ln1_b ln2_g ln2_b [L, h]
    qkv_w [L, 3h, h] qkv_b [L, 3h]   proj_w [L, h, h]  proj_b [L, h]
    fc_w  [L, f, h]  fc_b  [L, f]    out_w  [L, h, f]  out_b  [L, h]

``quant`` turns the reference into its own low-precision control: it
rounds the operands of the four GEMMs of every layer (QKV, attention
output, the two of the MLP), forward and backward, which is the set a
transformer's fp8 recipes quantise; ``None`` is the reference itself.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
              "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a Hugging Face GPT-2 ``config.json``."""
    h = config["n_embd"]
    return {"hidden": h, "layers": config["n_layer"], "heads": config["n_head"],
            "ffn": config.get("n_inner") or 4 * h,
            "positions": config["n_positions"],
            "vocab": config["vocab_size"],
            "vocab_padded": config["assumed"]["padded_vocab_size"],
            "eps": config["layer_norm_epsilon"],
            "init_std": config["initializer_range"],
            "gelu": config["activation_function"]}


def count_params(sz: dict, positions: bool = True) -> int:
    """Parameters of the model as trained (padded token table)."""
    h, f, L = sz["hidden"], sz["ffn"], sz["layers"]
    per_layer = 4 * h + 3 * h * h + 3 * h + h * h + h + f * h + f + h * f + h
    n = sz["vocab_padded"] * h + L * per_layer + 2 * h
    return n + (sz["positions"] * h if positions else 0)


def init_weights(key, sz: dict) -> dict:
    """Seeded weights: GPT-2's N(0, std) for matrices and tables, the
    residual-facing matrices scaled by 1/sqrt(2L); biases and LayerNorm
    offsets N(0, std), LayerNorm gains 1 + N(0, std) - a checkpoint's
    biases are not zero, and zero ones would hide a dropped bias."""
    h, f, L = sz["hidden"], sz["ffn"], sz["layers"]
    std = sz["init_std"]
    out_std = std / math.sqrt(2.0 * L)
    shapes = {
        "wte": ((sz["vocab_padded"], h), std, 0.0),
        "wpe": ((sz["positions"], h), std, 0.0),
        "lnf_g": ((h,), std, 1.0), "lnf_b": ((h,), std, 0.0),
        "ln1_g": ((L, h), std, 1.0), "ln1_b": ((L, h), std, 0.0),
        "ln2_g": ((L, h), std, 1.0), "ln2_b": ((L, h), std, 0.0),
        "qkv_w": ((L, 3 * h, h), std, 0.0), "qkv_b": ((L, 3 * h), std, 0.0),
        "proj_w": ((L, h, h), out_std, 0.0), "proj_b": ((L, h), std, 0.0),
        "fc_w": ((L, f, h), std, 0.0), "fc_b": ((L, f), std, 0.0),
        "out_w": ((L, h, f), out_std, 0.0), "out_b": ((L, h), std, 0.0),
    }
    w = {}
    for i, (name, (shape, s, mean)) in enumerate(sorted(shapes.items())):
        w[name] = mean + s * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
    return w


def weight_shardings(devices, sz: dict) -> dict:
    """Where the reference keeps its weights when it is given several
    chips: every leaf split over them along its first dimension after the
    layer stack's (the token and position tables along their rows), so that
    a model too large for one chip's memory, with its gradients and Adam's
    moments, fits.  The arithmetic is the same; XLA places it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)
    shapes = jax.eval_shape(lambda k: init_weights(k, sz),
                            jax.random.PRNGKey(0))
    out = {}
    for name, x in shapes.items():
        if name in LAYER_KEYS and x.shape[1] % n == 0:
            spec = P(None, "x")
        elif name in ("wte", "wpe") and x.shape[0] % n == 0:
            spec = P("x")
        else:
            spec = P()
        out[name] = NamedSharding(mesh, spec)
    return out


def fp_quant(exponent_bits: int, mantissa_bits: int, max_value: float):
    """Per-tensor scaled rounding to a small float format (amax scaling, as
    fp8 recipes do), returned in float32.  ``max_value`` is the format's
    largest finite number: past it ``reduce_precision`` gives infinity."""
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
# (IEEE's top exponent is kept for infinity: e4m3 tops out at 1.875 * 2**7.)
Quant = collections.namedtuple("Quant", "fwd bwd")
FP8 = Quant(fwd=fp_quant(4, 3, 240.0), bwd=fp_quant(5, 2, 57344.0))


@functools.lru_cache(maxsize=None)
def _quant_matmul(quant):
    """``x [b, s, i], w [o, i] -> [b, s, o]`` with every GEMM of the
    forward and backward passes on operands rounded by ``quant``."""
    def product(x, w):
        return jnp.einsum("bsi,oi->bso", x, w, precision=HIGHEST)

    @jax.custom_vjp
    def matmul(x, w):
        return product(quant.fwd(x), quant.fwd(w))

    def forward(x, w):
        xq, wq = quant.fwd(x), quant.fwd(w)
        return product(xq, wq), (xq, wq)

    def backward(kept, dy):
        xq, wq = kept
        dy = quant.bwd(dy)
        return (jnp.einsum("bso,oi->bsi", dy, wq, precision=HIGHEST),
                jnp.einsum("bso,bsi->oi", dy, xq, precision=HIGHEST))

    matmul.defvjp(forward, backward)
    return matmul


def _linear(x, w, b, quant):
    if quant is not None:
        return _quant_matmul(quant)(x, w) + b
    return jnp.einsum("...i,oi->...o", x, w, precision=HIGHEST) + b


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu(x, kind):
    """Hugging Face's ``gelu_new`` (tanh) or ``gelu`` (erf)."""
    if kind == "gelu_new":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if kind == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    raise ValueError(f"the reference has no activation {kind!r}")


def _block(x, lw, sz, quant):
    """One decoder block on ``x [b, s, h]``."""
    b, s, h = x.shape
    n = sz["heads"]
    d = h // n
    a = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], sz["eps"])
    qkv = _linear(a, lw["qkv_w"], lw["qkv_b"], quant).reshape(b, s, n, 3, d)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                        precision=HIGHEST) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v,
                     precision=HIGHEST).reshape(b, s, h)
    x = x + _linear(ctx, lw["proj_w"], lw["proj_b"], quant)
    m = _layer_norm(x, lw["ln2_g"], lw["ln2_b"], sz["eps"])
    m = _gelu(_linear(m, lw["fc_w"], lw["fc_b"], quant), sz["gelu"])
    return x + _linear(m, lw["out_w"], lw["out_b"], quant)


def hidden_states(w, tokens, sz, quant=None):
    """Final-LayerNormed hidden states ``[b, s, h]`` of ``tokens [b, s]``."""
    s = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:s][None]
    stack = {k: w[k] for k in LAYER_KEYS}

    @jax.checkpoint
    def body(x, lw):
        return _block(x, lw, sz, quant), None

    x, _ = jax.lax.scan(body, x, stack)
    return _layer_norm(x, w["lnf_g"], w["lnf_b"], sz["eps"])


def logits(w, tokens, sz, quant=None):
    """``[b, s, vocab_padded]`` logits, head tied to the token table."""
    return jnp.einsum("bsh,vh->bsv", hidden_states(w, tokens, sz, quant),
                      w["wte"], precision=HIGHEST)


def summed_loss(w, tokens, sz, quant=None):
    """Sum over rows and positions of the next-token cross entropy
    (position t predicts token t+1), over the padded vocabulary."""
    lg = logits(w, tokens, sz, quant)[:, :-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


@functools.partial(jax.jit, static_argnames=("sz_items", "quant"))
def _block_grad(w, tokens, sz_items, quant):
    return jax.value_and_grad(summed_loss)(w, tokens, dict(sz_items), quant)


def loss_and_grad(w, tokens, sz, rows_per_block, quant=None):
    """Mean loss over ``tokens [b, s]`` and its gradient, accumulated over
    blocks of rows so that the float32 logits of a block fit."""
    b, s = tokens.shape
    sz_items = tuple(sorted(sz.items()))
    total, grads = 0.0, None
    for i in range(0, b, rows_per_block):
        val, g = _block_grad(w, tokens[i:i + rows_per_block], sz_items, quant)
        total = total + val
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    count = b * (s - 1)
    return total / count, jax.tree_util.tree_map(lambda g: g / count, grads)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"))
def adam_step(w, g, m, v, t, lr, b1, b2, eps):
    """Adam with bias correction and no weight decay, ``t`` counted from 1."""
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v

    out = {k: leaf(w[k], g[k], m[k], v[k]) for k in w}
    return tuple({k: o[i] for k, o in out.items()} for i in range(3))


def compared_leaves(tree: dict, sz: dict) -> dict:
    """The leaves whose norms are compared: the weights' own, with the fused
    QKV matrix and bias split into their q, k and v parts - the key's bias
    has no gradient under softmax, and inside the fused leaf it would hide
    from the rule that leaves such leaves out.  Takes any tree of the
    weights' names whose leaves have the weights' sizes."""
    out = {k: x for k, x in tree.items() if not k.startswith("qkv_")}
    for name in ("qkv_w", "qkv_b"):
        parts = tree[name].reshape(sz["layers"], sz["heads"], 3, -1)
        for i, part in enumerate("qkv"):
            out[part + name[3:]] = parts[:, :, i]
    return out


def leaf_norms(tree: dict, sz: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x)))
            for k, x in compared_leaves(tree, sz).items()}


def delta_norms(a: dict, b: dict, sz: dict) -> dict:
    return leaf_norms({k: a[k].reshape(b[k].shape) - b[k] for k in a}, sz)


def train(w0, batches, sz, hyper, rows_per_block, quant=None, fault=None):
    """Follow ``len(batches)`` Adam steps from ``w0``.  Returns the loss of
    each step, the per-leaf norm of the first step's gradient and the
    per-leaf norm of the parameters' change over all the steps.

    ``fault`` plants one of the faults a trainer can have, for the tests
    that show the comparison catches it: ``"half_batch"`` takes the mean
    over the first half of each batch's rows only."""
    norms = jax.jit(lambda t: leaf_norms(t, sz))
    deltas = jax.jit(lambda a, b: delta_norms(a, b, sz))
    w = w0
    zeros = jax.tree_util.tree_map(jnp.zeros_like, w0)
    m, v = zeros, zeros
    losses, first = [], None
    for t, tokens in enumerate(batches, start=1):
        if fault == "half_batch":
            tokens = tokens[: tokens.shape[0] // 2]
        loss, g = loss_and_grad(w, tokens, sz, rows_per_block, quant)
        losses.append(float(loss))
        if first is None:
            first = {k: float(x) for k, x in norms(g).items()}
        w, m, v = adam_step(w, g, m, v, jnp.float32(t), hyper["lr"],
                            hyper["beta1"], hyper["beta2"], hyper["eps"])
        del g
    delta = {k: float(x) for k, x in deltas(w, w0).items()}
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}


@functools.partial(jax.jit, static_argnames=("sz_items", "quant"))
def _row_logits(w, tokens, sz_items, quant):
    return logits(w, tokens[None], dict(sz_items), quant)[0]


def _served_rows(w, prompt, served, sz, pad, quant):
    """Logits at the positions that produced ``served``: the forward pass
    over the prompt and the served tokens, padded to ``pad`` (causal, so the
    padding behind changes nothing)."""
    import numpy as np

    seq = np.zeros((pad,), np.int32)
    n = len(prompt) + len(served) - 1
    seq[:n] = np.concatenate([prompt, served])[:n]
    rows = _row_logits(w, jnp.asarray(seq), tuple(sorted(sz.items())), quant)
    return rows[len(prompt) - 1: n]


def served_token_gaps(w, prompt, served, sz, pad):
    """For each greedy served token, how far its logit lies below the
    reference's best at its position; 0 where the reference agrees."""
    rows = _served_rows(w, prompt, served, sz, pad, None)
    picked = jnp.take_along_axis(rows, jnp.asarray(served)[:, None], 1)[:, 0]
    return jnp.max(rows, axis=-1) - picked


def control_token_gaps(w, prompt, served, sz, pad, quant):
    """The same for the token that the forward pass in ``quant``'s precision
    puts first at each position of the same prompt and tokens."""
    rows = _served_rows(w, prompt, served, sz, pad, None)
    low = _served_rows(w, prompt, served, sz, pad, quant)
    picked = jnp.take_along_axis(rows, jnp.argmax(low, -1)[:, None], 1)[:, 0]
    return jnp.max(rows, axis=-1) - picked


@functools.partial(jax.jit, static_argnames=("sz_items", "quant"))
def _last_logits(w, tokens, last, sz_items, quant):
    hidden = hidden_states(w, tokens, dict(sz_items), quant)
    rows = hidden[jnp.arange(tokens.shape[0]), last]
    return jnp.einsum("nh,vh->nv", rows, w["wte"], precision=HIGHEST)


def last_logits(w, sequences, sz, pad, quant=None):
    """Logits ``[n, vocab_padded]`` that follow each of ``sequences`` (lists
    of token ids, none longer than ``pad``): one forward pass over all of
    them, padded behind."""
    import numpy as np

    tokens = np.zeros((len(sequences), pad), np.int32)
    for i, seq in enumerate(sequences):
        tokens[i, :len(seq)] = seq
    last = np.asarray([len(seq) - 1 for seq in sequences], np.int32)
    return _last_logits(w, jnp.asarray(tokens), jnp.asarray(last),
                        tuple(sorted(sz.items())), quant)
