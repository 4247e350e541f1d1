"""Training a model whose layers are of more than one kind.

:func:`build_gpt_3d` hands a ``TransformerConfig.hybrid`` here and returns
what this returns: the same ``(init_fn, make_loss_fn, make_train_step)``,
so a caller builds, steps and reads :class:`TrainStats` exactly as it does
for a model of identical layers (FusedAdam, the sentinel, donation).

Layers that are not alike cannot be one stacked ``[vpp, pp, ...]`` tree, so
the parameters are :class:`HybridParams` (a tuple of per-layer dicts, the
tree ``serving.HybridDecodeModel`` serves) and the forward pass walks them
in order, each layer under ``jax.checkpoint``:

- attention through the flash kernels with the layer kind's window and its
  K/V heads as they are (grouped-query heads are never repeated in HBM),
  under the scope ``flash_window`` or ``flash_full`` that names the three
  kernels in a device trace; QK-norm, rotary positions or none, and the
  output gate (scope ``attention_gate``) as the kind says;
- the dense SwiGLU, or :func:`apex_tpu.transformer.moe.held_experts_ffn`
  with its shared expert, route scale and epsilon; each expert layer's
  pairs per held expert come back with the loss;
- with ``sandwich_norm`` each sublayer's output is normed before it is
  added; the embedding is multiplied by ``embedding_multiplier``.

Microbatches are a ``lax.scan`` whose gradients are added to one sum as they
are made (``loss_and_routing``'s own VJP: no second gradient tree is ever
held); the logits and the
loss are computed in blocks of rows under ``jax.checkpoint``, so ``[tokens,
vocab]`` in float32 and its gradient are never whole.  Parameters are
float32, the matmuls' operands ``config.dtype``, the residual stream, norm
statistics, router and loss float32.

One chip, dp1 x pp1 x tp1: a pipeline over unlike layers, tensor or sequence
parallelism, packed inputs, dropout, sinks and q/k beside v widths (the
flash kernels take neither) are refused by name (ROADMAP R5, R10).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.normalization.fused_layer_norm import fused_rms_norm_affine
from apex_tpu.observability.spans import named_span
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.parallel.mesh import (
    DATA_AXIS,
    PIPELINE_AXIS,
    TENSOR_AXIS,
)
from apex_tpu.transformer import moe
from apex_tpu.transformer.rope import apply_rotary_packed, rotary_cos_sin
from apex_tpu.transformer.testing.standalone_transformer_lm import (
    HybridParams,
    TransformerConfig,
)

__all__ = ["build_hybrid_train", "hybrid_layer_shapes"]

LOSS_ROWS = 1024        # rows of logits computed at once


@jax.custom_vjp
def _adding_to(param, summed):
    """``param``, for a pass whose gradient with respect to ``summed`` is
    ``summed`` plus the gradient that reaches ``param``."""
    del summed
    return param


_adding_to.defvjp(lambda param, summed: (param, summed),
                  lambda summed, ct: (None, summed + ct.astype(summed.dtype)))


def _refusals(cfg: TransformerConfig, mesh, packed_inputs, block_diagonal,
              remat_ticks):
    """What this trainer cannot do yet, each with its name."""
    spec = cfg.hybrid
    for axis, what in ((PIPELINE_AXIS, "a pipeline over unlike layers "
                        "(pp > 1)"), (TENSOR_AXIS, "tensor parallelism "
                                      "(tp > 1)"),
                       (DATA_AXIS, "data parallelism (dp > 1)")):
        if mesh.shape.get(axis, 1) > 1:
            yield what
    if cfg.sequence_parallel:
        yield "sequence_parallel"
    if cfg.context_axis is not None:
        yield "context_axis"
    if packed_inputs or block_diagonal:
        yield "packed_inputs"
    if remat_ticks is not None:
        yield "remat_ticks"
    if cfg.hidden_dropout or cfg.attention_dropout:
        yield "dropout (hidden_dropout, attention_dropout)"
    if cfg.fp8:
        yield "fp8"
    if cfg.num_experts is not None:
        yield "num_experts (the Switch layer: hybrid.experts describes the "\
            "expert layers)"
    for kind in spec.kinds:
        if kind.sink:
            yield f"{kind.name}.sink (the flash kernels take no sinks)"
        if kind.k_dim != kind.v_dim:
            yield (f"{kind.name}.k_dim != v_dim (the flash kernels take one "
                   "head width)")
        if kind.latent:
            yield (f"{kind.name}.latent_rank (latent attention is served, "
                   "not trained)")
    if spec.experts is not None and spec.experts.routing:
        yield ("experts." + ", experts.".join(sorted(spec.experts.routing))
               + " (softmax and group-limited routing are served, not "
               "trained)")


def hybrid_layer_shapes(cfg: TransformerConfig, layer: int) -> dict:
    """``{name: (shape, kind of leaf)}`` of one layer's parameters, the
    kind being ``"norm"``, ``"in"`` (a matrix that reads the residual
    stream), ``"out"`` (one that writes to it) or ``"bias"``."""
    spec, h = cfg.hybrid, cfg.hidden_size
    kind = spec.kinds[spec.layer_kinds[layer]]
    n, g, dk, dv = kind.num_heads, kind.kv_heads, kind.k_dim, kind.v_dim
    shapes = {"norm1": ((h,), "norm"), "norm2": ((h,), "norm"),
              "wq": ((h, n * dk), "in"), "wk": ((h, g * dk), "in"),
              "wv": ((h, g * dv), "in"), "wo": ((n * dv, h), "out")}
    if kind.qk_norm:
        shapes.update(q_norm=((dk,), "norm"), k_norm=((dk,), "norm"))
    if kind.gate:
        shapes["wg"] = ((h, n * dv), "in")
    if spec.sandwich_norm:
        shapes.update(post_attn_norm=((h,), "norm"),
                      post_ffn_norm=((h,), "norm"))
    if spec.layer_experts[layer]:
        ex = spec.experts
        f, held = ex.ffn_size, ex.held[1]
        shapes.update(router=((h, ex.n_experts), "in"),
                      router_bias=((ex.n_experts,), "bias"),
                      experts_gate_up=((held, h, 2 * f), "in"),
                      experts_down=((held, f, h), "out"))
        if ex.shared_experts:
            fs = ex.shared_experts * f
            shapes.update(shared_gate_up=((h, 2 * fs), "in"),
                          shared_down=((fs, h), "out"))
    else:
        f = cfg.ffn_size
        shapes.update(ffn_gate_up=((h, 2 * f), "in"),
                      ffn_down=((f, h), "out"))
    return shapes


def build_hybrid_train(cfg: TransformerConfig, *, num_chunks: int = 1,
                       num_microbatches: int = 2, mesh=None,
                       packed_inputs: bool = False,
                       block_diagonal: bool = False, remat_ticks=None):
    """``(init_fn, make_loss_fn, make_train_step)`` as
    :func:`build_gpt_3d` documents them, for ``cfg.hybrid``."""
    from apex_tpu.transformer.testing.gpt_parallel_train import (
        make_train_step)

    del num_chunks          # no virtual stages: the layers are walked
    if mesh is None:
        from apex_tpu.parallel.mesh import get_mesh
        mesh = get_mesh()
    refused = list(_refusals(cfg, mesh, packed_inputs, block_diagonal,
                             remat_ticks))
    if refused:
        raise NotImplementedError(
            "the trainer of hybrid layers runs on dp1 x pp1 x tp1 and does "
            "not take: " + "; ".join(refused))
    spec, dtype = cfg.hybrid, cfg.dtype
    eps, h = cfg.layernorm_epsilon, cfg.hidden_size
    vocab = cfg.padded_vocab_size
    n_expert_layers = sum(spec.layer_experts)

    def init_fn(rng, sample_tokens):
        """Freshly initialised float32 parameters (matrices N(0, std), those
        that write to the residual stream / sqrt(2 L), norm gains 1, the
        selection bias 0) and their specs: every leaf whole on the chip."""
        del sample_tokens
        std = cfg.init_method_std
        scale = {"in": std, "out": std / math.sqrt(2.0 * cfg.num_layers)}

        def make(rng):
            def leaf(key, shape, what):
                if what == "norm":
                    return jnp.ones(shape, jnp.float32)
                if what == "bias":
                    return jnp.zeros(shape, jnp.float32)
                return scale[what] * jax.random.normal(key, shape,
                                                       jnp.float32)

            layers = []
            for layer in range(cfg.num_layers):
                lkey = jax.random.fold_in(rng, 1000 + layer)
                layers.append({
                    name: leaf(jax.random.fold_in(lkey, i), shape, what)
                    for i, (name, (shape, what)) in enumerate(sorted(
                        hybrid_layer_shapes(cfg, layer).items()))})
            return HybridParams(
                embedding=leaf(jax.random.fold_in(rng, 0), (vocab, h), "in"),
                layers=tuple(layers),
                final_norm=jnp.ones((h,), jnp.float32),
                head=leaf(jax.random.fold_in(rng, 1), (h, vocab), "in"))

        params = jax.jit(make)(rng)
        return params, jax.tree_util.tree_map(lambda _: P(), params)

    # ------------------------------------------------------------ forward

    def norm(x, gain, width=h):
        return fused_rms_norm_affine(x, gain, width, eps)

    def mm(x, w):
        return jnp.dot(x, w.astype(dtype), preferred_element_type=jnp.float32)

    def attention(lp, kind, a, mb, s):
        """``a [mb * s, hidden]`` (normed, ``dtype``) -> ``[mb * s,
        hidden]`` float32, before any post-norm."""
        n, g, dk, dv = kind.num_heads, kind.kv_heads, kind.k_dim, kind.v_dim
        q = mm(a, lp["wq"]).reshape(-1, n, dk)
        k = mm(a, lp["wk"]).reshape(-1, g, dk)
        v = (mm(a, lp["wv"]) * spec.value_scale).reshape(-1, g, dv)
        if kind.qk_norm:
            q = norm(q, lp["q_norm"], dk)
            k = norm(k, lp["k_norm"], dk)
        if kind.rotary_dim:
            positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), mb)
            cos, sin = rotary_cos_sin(positions, kind.rotary_dim,
                                      kind.rotary_base, jnp.float32)
            q, k = (apply_rotary_packed(x[:, None], cos[:, None],
                                        sin[:, None])[:, 0] for x in (q, k))

        def heads_first(x):                     # [mb * s, heads, d] -> BHSD
            return x.astype(dtype).reshape(mb, s, -1, x.shape[-1]).transpose(
                0, 2, 1, 3)

        with named_span("flash_full" if kind.window is None
                        else "flash_window"):
            ctx = flash_attention(heads_first(q), heads_first(k),
                                  heads_first(v), causal=True,
                                  window=kind.window)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(-1, n * dv)
        if kind.gate:
            with named_span("attention_gate"):
                ctx = (ctx.astype(jnp.float32) * jax.nn.sigmoid(
                    mm(a, lp["wg"]))).astype(dtype)
        return mm(ctx, lp["wo"])

    def layer_fn(layer, lp, x, mb, s):
        """One layer on the float32 residual stream ``x [mb * s, hidden]``;
        returns it with the layer's pairs per held expert and its router's
        choices ``[mb * s, top_k]`` (both empty for a dense layer)."""
        kind = spec.kinds[spec.layer_kinds[layer]]
        out = attention(lp, kind, norm(x, lp["norm1"]).astype(dtype), mb, s)
        if spec.sandwich_norm:
            out = norm(out, lp["post_attn_norm"])
        x = x + out
        m = norm(x, lp["norm2"]).astype(dtype)
        if spec.layer_experts[layer]:
            ex = spec.experts
            shared = ((lp["shared_gate_up"].astype(dtype),
                       lp["shared_down"].astype(dtype))
                      if ex.shared_experts else None)
            out, pairs, chosen, _ = moe.held_experts_ffn(
                m, lp["router"], lp["router_bias"],
                lp["experts_gate_up"].astype(dtype),
                lp["experts_down"].astype(dtype), top_k=ex.top_k,
                held=ex.held, route_eps=ex.route_eps,
                route_scale=ex.route_scale, shared=shared)
        else:
            out = moe.swiglu(m, lp["ffn_gate_up"].astype(dtype),
                             lp["ffn_down"].astype(dtype))
            pairs = chosen = jnp.zeros((0,), jnp.int32)
        if spec.sandwich_norm:
            out = norm(out, lp["post_ffn_norm"])
        return x + out, (pairs, chosen)

    def summed_loss(hidden, head, targets, counted):
        """Sum over the counted rows of the cross entropy of ``hidden
        [rows, hidden]`` against ``targets [rows]``, the logits made
        ``LOSS_ROWS`` rows at a time and made again in the backward pass."""
        rows = hidden.shape[0]
        block = math.gcd(rows, LOSS_ROWS)

        @jax.checkpoint
        def block_loss(hid, tgt, cnt):
            logits = jnp.dot(hid, head, preferred_element_type=jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
            return jnp.sum(jnp.where(cnt, logz - picked, 0.0))

        def body(total, xs):
            return total + block_loss(*xs), None

        total, _ = jax.lax.scan(body, jnp.float32(0.0), tuple(
            x.reshape((rows // block, block) + x.shape[1:])
            for x in (hidden, targets, counted)))
        return total

    def microbatch_loss(params, tokens):
        """Mean next-token loss of ``tokens [mb, s]``, the expert layers'
        pairs ``[expert layers, held]`` and their routers' choices
        ``[expert layers, mb * s, top_k]``."""
        mb, s = tokens.shape
        flat = tokens.reshape(-1)
        x = params.embedding[flat].astype(jnp.float32)
        if spec.embedding_multiplier != 1.0:
            x = x * spec.embedding_multiplier
        routed = []
        for layer, lp in enumerate(params.layers):
            x, counts = jax.checkpoint(
                functools.partial(layer_fn, layer, mb=mb, s=s))(lp, x)
            if spec.layer_experts[layer]:
                routed.append(counts)
        hidden = norm(x, params.final_norm).astype(dtype)
        # position t predicts token t + 1; a row's last position predicts
        # nothing
        targets = jnp.roll(tokens, -1, axis=1).reshape(-1)
        counted = jnp.tile(jnp.arange(s) < s - 1, mb)
        loss = summed_loss(hidden, params.head.astype(dtype), targets,
                           counted) / (mb * (s - 1))
        if not routed:
            return loss, ()
        return loss, jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                            *routed)

    def microbatches_of(tokens):
        batch, s = tokens.shape
        if batch % num_microbatches:
            raise ValueError(f"batch ({batch}) is not a whole number of "
                             f"{num_microbatches} microbatches")
        return tokens.reshape(num_microbatches, -1, s)

    def routing_stats(routed):
        if not n_expert_layers:
            return {}
        pairs, chosen = routed
        return {"moe_pairs": pairs, "moe_choices": chosen}

    @jax.custom_vjp
    def loss_and_routing(params, tokens):
        """``(loss, {"moe_pairs": [m, expert layers, held], "moe_choices":
        [m, expert layers, mb * s, top_k]})``; the second ``{}`` for a model
        without expert layers."""
        def body(total, mb_tokens):
            loss, routed = microbatch_loss(params, mb_tokens)
            return total + loss, routed

        total, routed = jax.lax.scan(body, jnp.float32(0.0),
                                     microbatches_of(tokens))
        return total / num_microbatches, routing_stats(routed)

    # Differentiated, the microbatches' gradients are added up *as they are
    # made*.  Left to the transposed scan, each microbatch's gradient is a
    # whole second tree beside the sum (2.8 GB at 700 M parameters, which
    # one chip beside float32 weights and two Adam moments does not have).
    # So the forward rule runs each microbatch's own backward pass with the
    # sum so far riding in as an argument: every parameter passes through
    # ``_adding_to`` on its way in, whose gradient *with respect to the sum*
    # is the sum plus the parameter's, formed where that gradient is made.
    def forward(params, tokens):
        def body(carry, mb_tokens):
            total, summed = carry

            def of_sum(summed):
                loss, routed = microbatch_loss(
                    jax.tree_util.tree_map(_adding_to, params, summed),
                    mb_tokens)
                return loss / num_microbatches, routed

            (loss, routed), summed = jax.value_and_grad(
                of_sum, has_aux=True)(summed)
            return (total + loss, summed), routed

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (total, summed), routed = jax.lax.scan(
            body, (jnp.float32(0.0), zeros), microbatches_of(tokens))
        return (total, routing_stats(routed)), summed

    def backward(summed, cts):
        return jax.tree_util.tree_map(lambda g: g * cts[0], summed), None

    loss_and_routing.defvjp(forward, backward)

    def make_loss_fn(param_specs):
        del param_specs
        return lambda params, tokens: loss_and_routing(params, tokens)[0]

    def make_aux_loss_fn(param_specs):
        del param_specs
        return loss_and_routing

    return init_fn, make_loss_fn, functools.partial(
        make_train_step, mesh, make_loss_fn, make_aux_loss_fn,
        aux_stats=lambda aux: aux)
