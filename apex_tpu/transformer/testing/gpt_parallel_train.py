"""Full 3D-parallel GPT training step: dp × pp(×vpp) × tp(+sp).

The integration point of the whole runtime — the analog of the reference's
GPT pipeline test/production shape (``tests/L0/run_transformer/
test_pipeline_parallel_fwd_bwd.py``, ``gpt_scaling_test.py``): vocab/tensor-
parallel embedding and layers (``tp`` axis, Megatron sequence parallelism),
the rotation pipeline over ``pp`` with virtual chunks, data parallelism over
``dp``, vocab-parallel cross entropy, and a fused optimizer — all inside
ONE ``shard_map`` over the mesh, with *honest* per-leaf PartitionSpecs so
every gradient reduction (dp grad psum, SP replicated-param psum) is
inserted by the shard_map transpose rather than hand-written (see
:mod:`apex_tpu.transformer.tensor_parallel.partition`).

Layer-stack layout: per-layer params are stacked virtual-stage-major
``[L, ...]`` and reshaped to ``[vpp, pp, ...]`` so the ``pp`` dim shards
(chunk ``c`` of stage ``s`` = virtual stage ``c*pp + s`` — the interleaved
schedule's chunk mapping, ``fwd_bwd_pipelining_with_interleaving.py:221``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.ops.softmax import AttnMaskType
from apex_tpu.parallel import collectives as cc
from apex_tpu.parallel.mesh import (
    DATA_AXIS,
    PIPELINE_AXIS,
    TENSOR_AXIS,
)
from apex_tpu.transformer.pipeline_parallel.schedules import (
    pipeline_apply,
    split_into_microbatches,
)
from apex_tpu.transformer.tensor_parallel import infer_param_specs
from apex_tpu.transformer.layers.layer_norm import FusedLayerNorm
from apex_tpu.transformer.testing.standalone_gpt import gpt_next_token_loss
from apex_tpu.transformer.testing.standalone_transformer_lm import (
    Embedding,
    ParallelTransformerLayer,
    TransformerConfig,
    parallel_lm_logits,
)

__all__ = ["GPT3DParams", "build_gpt_3d", "gpt3d_logical_folds"]


class GPT3DParams(NamedTuple):
    embedding: dict
    layers: dict      # stacked [vpp, pp, ...]
    final_ln: dict


def gpt3d_logical_folds(tree):
    """Fold-count pytree for :func:`apex_tpu.resilience.reshard.
    build_spec`: same structure as ``tree``, ``2`` on every leaf of a
    :class:`GPT3DParams` ``layers`` stack, ``0`` elsewhere.

    The layer stack is ``[vpp, pp, ...]`` — a plain reshape of the
    virtual-stage-major ``[L, ...]`` logical stack (chunk ``c`` of stage
    ``s`` is virtual stage ``c*pp + s``, so row-major merge/split IS the
    interleaved schedule's chunk mapping).  Annotating the two leading
    dims as one folded logical axis lets a checkpoint written at
    ``(vpp, pp) = (1, 2)`` restore onto ``(2, 1)`` — the tp/pp
    elastic-resume transition — by merging to ``[L]`` and re-splitting.
    Works on any pytree *containing* GPT3DParams nodes (the packed
    train state: params, a mirroring ``OptState``, sentinel state).
    """
    def mark(node):
        if isinstance(node, GPT3DParams):
            def const(sub, v):
                return jax.tree_util.tree_map(lambda _: v, sub)

            return GPT3DParams(embedding=const(node.embedding, 0),
                               layers=const(node.layers, 2),
                               final_ln=const(node.final_ln, 0))
        return 0

    return jax.tree_util.tree_map(
        mark, tree, is_leaf=lambda x: isinstance(x, GPT3DParams))


def _prepend(spec_tree, *dims):
    return jax.tree_util.tree_map(
        lambda s: P(*dims, *tuple(s)), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_gpt_3d(
    config: TransformerConfig,
    *,
    num_chunks: int = 1,
    num_microbatches: int = 2,
    mesh=None,
    dp_axis: str = DATA_AXIS,
    pp_axis: str = PIPELINE_AXIS,
    tp_axis: str = TENSOR_AXIS,
    moe_aux_coeff: float = 1e-2,
    remat_ticks=None,
    packed_inputs: bool = False,
    block_diagonal: bool = False,
):
    """Return ``(init_fn, train_step, param_specs_fn)``.

    - ``init_fn(rng, sample_tokens) -> (params, param_specs)`` — global
      arrays with their PartitionSpec tree (params built under a tp-only
      shard_map so vocab/width shards initialize per-rank).
    - ``train_step(params, opt_state, tokens, opt) -> (params, opt_state,
      loss)`` — call under ``jax.jit``; internally one shard_map over
      (dp, pp, tp).

    ``config.num_layers`` must equal ``pp * num_chunks`` (one transformer
    layer per virtual stage); ``tokens: [global_batch, seq]`` sharded on dp.

    A ``config.hybrid`` (layers of more than one kind: window beside full
    attention, an expert layer beside a dense one) is trained by
    :mod:`.hybrid_train` behind the same three results, on dp1 x pp1 x tp1;
    what it cannot do yet it refuses by name.

    ``remat_ticks``: forward to :func:`pipeline_apply` for the 1F1B-class
    live-activation bound (grouped-tick remat); the train step must run
    under ``jax.jit`` (it should anyway).

    ``packed_inputs``: the real-data ingestion mode for
    :class:`~apex_tpu.data.sequence.PackedSequenceLoader` streams — the
    ``tokens`` argument of the loss/step becomes the loader's
    ``(tokens [b, s], segment_ids [b, s])`` pair (both dp-sharded), and
    the next-token loss is masked with
    :func:`~apex_tpu.data.sequence.segment_loss_mask` so no position
    predicts across a document boundary or into padding.  The loss
    becomes masked-sum / masked-count (accumulated across microbatches),
    and by default the attention stays plain causal (the standard packed
    pre-training trade).  Everything else — pipeline, sentinel,
    telemetry, collective budget — is unchanged.

    ``block_diagonal`` (requires ``packed_inputs`` and
    ``config.use_flash_attention``): close the packed trade — the
    per-microbatch segment ids ride the pipelined activation pytree
    (rotating with the microbatch they describe; int leaves carry no
    tangent, so the backward schedule is untouched) and feed the flash
    kernel's segment masking, so attention is **block-diagonal causal**
    — no position attends back into the previous document.  The fused
    softmax core has no segment mechanism (it would silently ignore
    them), hence the flash requirement.  Full-coverage segments (one
    document spanning the row) reproduce the plain-causal forward
    bitwise: the combined causal∧same-segment mask degenerates to the
    causal mask and the kernel arithmetic is unchanged
    (``tests/test_sequence_data.py``).
    """
    cfg = config
    if cfg.hybrid is not None:
        # layers of more than one kind cannot be one stacked tree: their
        # trainer walks them in order, behind this same entry
        from apex_tpu.transformer.testing.hybrid_train import (
            build_hybrid_train)

        return build_hybrid_train(
            cfg, num_chunks=num_chunks, num_microbatches=num_microbatches,
            mesh=mesh, packed_inputs=packed_inputs,
            block_diagonal=block_diagonal, remat_ticks=remat_ticks)
    if block_diagonal:
        if not packed_inputs:
            raise ValueError(
                "block_diagonal requires packed_inputs=True — the segment "
                "ids that define the blocks arrive with the packed batch")
        if not cfg.use_flash_attention:
            raise ValueError(
                "block_diagonal requires config.use_flash_attention: the "
                "fused-softmax attention core has no segment-mask "
                "mechanism and would silently ignore the ids")
    if mesh is None:
        from apex_tpu.parallel.mesh import get_mesh
        mesh = get_mesh()
    pp = mesh.shape[pp_axis]
    vpp = num_chunks
    if cfg.num_layers != pp * vpp:
        raise ValueError(
            f"num_layers ({cfg.num_layers}) != pp*vpp ({pp}*{vpp})"
        )

    embed = Embedding(cfg)
    layer = ParallelTransformerLayer(
        cfg, self_attn_mask_type=AttnMaskType.causal
    )
    final_ln = FusedLayerNorm(cfg.hidden_size, eps=cfg.layernorm_epsilon)

    def init_fn(rng, sample_tokens):
        mb_tokens = sample_tokens[: max(1, sample_tokens.shape[0]
                                        // num_microbatches)]

        def local_init(tokens):
            e = embed.init(rng, tokens)["params"]
            h = embed.apply({"params": e}, tokens)
            per_layer = [
                layer.init(jax.random.fold_in(rng, i), h, None)["params"]
                for i in range(cfg.num_layers)
            ]
            stacked = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *per_layer
            )
            ln = final_ln.init(jax.random.fold_in(rng, 10_000), h)["params"]
            return e, stacked, ln

        shapes = jax.eval_shape(local_init, mb_tokens)
        ep_axis = cfg.expert_axis
        e_specs = infer_param_specs(shapes[0], axis=tp_axis)
        l_specs = _prepend(infer_param_specs(
            jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                shapes[1],
            ), axis=tp_axis, ep_axis=ep_axis
        ), None)  # [L, ...] replicated stack dim at init time
        ln_specs = jax.tree_util.tree_map(lambda _: P(), shapes[2])

        # jitted: run eagerly, a shard_map dispatches (and compiles) every
        # initializer op by itself — over a thousand tiny programs
        e, stacked, ln = jax.jit(cc.shard_over(
            local_init, mesh=mesh, in_specs=(P(),),
            out_specs=(e_specs, l_specs, ln_specs),
        ))(mb_tokens)

        # [L, ...] virtual-stage major -> [vpp, pp, ...]; pp dim shards.
        stacked = jax.tree_util.tree_map(
            lambda l: l.reshape((vpp, pp) + l.shape[1:]), stacked
        )
        layer_specs = _prepend(infer_param_specs(
            jax.tree_util.tree_map(lambda l: l[0, 0], stacked),
            axis=tp_axis, ep_axis=cfg.expert_axis
        ), None, pp_axis)
        # the eager reshape leaves the stack replicated over pp; place it
        # as its spec says, so init holds one stage's layers per device
        stacked = jax.device_put(stacked, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), layer_specs,
            is_leaf=lambda x: isinstance(x, P)))

        params = GPT3DParams(embedding=e, layers=stacked, final_ln=ln)
        specs = GPT3DParams(embedding=e_specs, layers=layer_specs,
                            final_ln=ln_specs)
        return params, specs

    def _local_loss(p: GPT3DParams, batch, with_aux: bool = False):
        """Mean LM loss of the local dp shard; runs with dp/pp/tp bound.
        With ``packed_inputs`` the batch is ``(tokens, segment_ids)`` and
        the mean is the segment-masked one (see :func:`build_gpt_3d`).

        Returns a ``(1,)``-shaped array, NOT a scalar: jax 0.4.x's
        old-style shard_map cannot name-check rank-0 values crossing the
        shard_map boundary under ``value_and_grad`` (scalar residual
        out-names trip ``_check_names`` with a ``_SpecError``; the
        promotion pass misses forwarded scalars), so every scalar on the
        loss tail keeps a singleton axis until outside the shard_map.

        ``with_aux=True`` (telemetry): returns ``(1 + m,)`` — the loss
        followed by the per-microbatch MoE aux vector, ``stop_gradient``
        -cut so the backward program is byte-for-byte the bare one.  Any
        collective the aux vector needs is the *widened* form of one the
        bare path already performs (never an extra op — the
        instrumented/bare HLO compare in tests/test_observability.py)."""
        if packed_inputs:
            tokens, segments = batch
            seg_mbs = split_into_microbatches(segments, num_microbatches)
        else:
            tokens = batch
        mbs = split_into_microbatches(tokens, num_microbatches)

        def embed_one(t):
            return embed.apply({"params": p.embedding}, t)

        h = jax.vmap(embed_one)(mbs)  # [m, s(/tp), mb, hid]
        # MoE aux loss rides the pipeline as a per-microbatch (1,)-shaped
        # slot in the activation pytree (stage output structure stays
        # homogeneous); dense configs carry a zero.  (1,) and not rank-0
        # per tick for the same _check_names reason as the loss below.
        aux0 = jnp.zeros((num_microbatches, 1), jnp.float32)

        if block_diagonal:
            # Segment ids ride the activation pytree so each microbatch's
            # ids rotate with its activations through the schedule (int32,
            # tangent-free — the transposed pipeline is unchanged); every
            # stage feeds them to the flash kernel's segment masking.
            def stage_fn(lp, xa):
                x, aux, seg = xa
                y, mut = layer.apply({"params": lp}, x, None,
                                     segment_ids=seg,
                                     mutable=["losses"])
                from apex_tpu.transformer.moe import collect_moe_aux

                return y, aux + collect_moe_aux(mut), seg

            out, aux_out, _ = pipeline_apply(
                stage_fn, p.layers, (h, aux0, seg_mbs), axis=pp_axis,
                num_chunks=vpp, params_already_local=True,
                remat_ticks=remat_ticks,
            )
        else:
            def stage_fn(lp, xa):
                x, aux = xa
                y, mut = layer.apply({"params": lp}, x, None,
                                     mutable=["losses"])
                from apex_tpu.transformer.moe import collect_moe_aux

                return y, aux + collect_moe_aux(mut)

            out, aux_out = pipeline_apply(
                stage_fn, p.layers, (h, aux0), axis=pp_axis,
                num_chunks=vpp, params_already_local=True,
                remat_ticks=remat_ticks,
            )

        def logits_of(hid):
            hid = final_ln.apply({"params": p.final_ln}, hid)
            return parallel_lm_logits(
                hid, p.embedding["word_embeddings"]["embedding"], cfg
            )

        if packed_inputs:
            from apex_tpu.data.sequence import segment_loss_mask

            def head_one(hid, t, seg):
                per_tok = gpt_next_token_loss(logits_of(hid), t, cfg)
                m = segment_loss_mask(seg)
                # (1,)-shaped like every scalar on the loss tail (the
                # old-shard_map _check_names constraint below)
                return (jnp.sum(per_tok * m).reshape(1),
                        jnp.sum(m).reshape(1))

            sums, counts = jax.vmap(head_one)(out, mbs, seg_mbs)
            # Leave the shard as [masked_sum, masked_count] — the
            # DIVISION happens outside the dp reduction (make_loss_fn):
            # a dp mean of per-shard ratios would equal-weight shards
            # whatever their real-token count, but mean-of-sums over
            # mean-of-counts is exactly global-sum/global-count (the dp
            # divisor cancels), so unevenly padded shards weigh by
            # their real tokens.
            ce = jnp.concatenate([jnp.sum(sums).reshape(1),
                                  jnp.maximum(jnp.sum(counts),
                                              1.0).reshape(1)])
        else:
            def head_one(hid, t):
                return jnp.mean(gpt_next_token_loss(logits_of(hid), t, cfg))

            losses = jax.vmap(head_one)(out, mbs)
            ce = jnp.mean(losses).reshape(1)
        # Telemetry rider: the per-microbatch aux vector is observational
        # only — stop_gradient keeps the differentiated subgraph (and so
        # the grads, bit for bit) identical to the bare path.  Dense
        # configs have no MoE aux: report zeros WITHOUT reading the
        # pipeline's aux carry — a dense bare step never consumes it, so
        # XLA DCEs its rotation ppermute, and reading it here would
        # resurrect a collective the bare step doesn't perform (the
        # instrumented/bare HLO compare in tests/test_observability.py).
        if not with_aux:
            aux_mb = None
        elif cfg.num_experts is not None:
            aux_mb = jax.lax.stop_gradient(
                aux_out.reshape(num_microbatches))
        else:
            aux_mb = jnp.zeros((num_microbatches,), jnp.float32)
        if cfg.num_experts is not None:
            aux_term = jnp.mean(aux_out).reshape(1)
            if cfg.tensor_axis is not None:
                # Under SP each tp rank routed a different sequence shard,
                # so its aux scalar differs; ce is tp-replicated (vocab-
                # parallel CE psums over tp) and the loss leaves this
                # shard_map with a replicated out-spec — average aux over
                # tp so the replication contract stays honest
                # (tensor_parallel/partition.py docstring).
                if with_aux:
                    # ONE tp reduction either way: the aux telemetry rides
                    # the existing (1,) pmean as extra payload (element 0
                    # is the same value bitwise — pmean is elementwise).
                    red = cc.all_reduce(
                        jnp.concatenate([aux_term, aux_mb]),
                        tp_axis, "mean")
                    aux_term, aux_mb = red[:1], red[1:]
                else:
                    aux_term = cc.all_reduce(aux_term, tp_axis, "mean")
            if packed_inputs:
                # packed ce is [sum, count] — the aux term cannot be
                # added to a sum; it rides out as a third element and is
                # composed after the division (make_loss_fn)
                ce = jnp.concatenate([ce, aux_term])
            else:
                ce = ce + moe_aux_coeff * aux_term
        if with_aux:
            return jnp.concatenate([ce, aux_mb])
        return ce

    def _batch_spec():
        """dp-sharded spec for the batch argument — a single tokens array,
        or the (tokens, segments) pair under ``packed_inputs``."""
        if packed_inputs:
            return (P(dp_axis), P(dp_axis))
        return P(dp_axis)

    def make_loss_fn(param_specs):
        """Global (dp-mean) loss over global arrays.

        ``jax.grad`` of THIS function is the supported way to train: the
        shard_map transpose then inserts every cross-rank gradient
        reduction — dp psum for all params, tp psum for SP-replicated
        norms/biases — because the specs tell the truth about replication
        (tensor_parallel/partition.py).  Taking grads *inside* the
        shard_map instead would silently drop the dp reduction.

        The loss leaves the shard_map body as a ``(1,)``-shaped array with
        an explicit replicated spec and is squeezed back to a scalar
        *outside*: jax 0.4.x's ``jax.experimental.shard_map`` partial-eval
        (staging under ``value_and_grad``) runs ``_check_names`` over the
        body's outputs and trips a ``_SpecError`` on a rank-0 residual
        out-name — a scalar output has no dimension to carry the vma
        names, while the ``(1,)`` form checks cleanly on every jax version
        we shim (new shard_map accepts both).
        """
        inner = cc.shard_over(
            lambda p, t: cc.all_reduce(
                _local_loss(p, t), dp_axis, "mean"),
            mesh=mesh,
            in_specs=(param_specs, _batch_spec()),
            out_specs=P(None),
        )

        def loss_fn(params, tokens):
            vec = inner(params, tokens)
            if not packed_inputs:
                return jnp.squeeze(vec, axis=0)
            # [sum, count(, aux_term)] dp-mean-reduced: mean-of-sums /
            # mean-of-counts IS global-sum/global-count (dp cancels) —
            # the exact masked mean, however unevenly padding lands
            loss = vec[0] / vec[1]
            if cfg.num_experts is not None:
                loss = loss + moe_aux_coeff * vec[2]
            return loss

        return loss_fn

    def make_aux_loss_fn(param_specs):
        """Telemetry variant of :func:`make_loss_fn`: returns
        ``loss_fn(params, tokens) -> (loss, aux_mb)`` with ``aux_mb``
        the dp-mean per-microbatch MoE aux vector ``[m]`` (zeros for
        dense configs), for ``jax.value_and_grad(..., has_aux=True)``.

        Same collective budget as the bare loss: the aux vector rides
        the existing dp pmean of the ``(1,)`` loss as a widened
        ``(1+m,)`` payload, and is ``stop_gradient``-cut inside — so the
        differentiated program (and the grads, bit for bit) is the bare
        one."""
        inner = cc.shard_over(
            lambda p, t: cc.all_reduce(
                _local_loss(p, t, with_aux=True), dp_axis, "mean"),
            mesh=mesh,
            in_specs=(param_specs, _batch_spec()),
            out_specs=P(None),
        )

        def loss_fn(params, tokens):
            vec = inner(params, tokens)
            if not packed_inputs:
                return vec[0], vec[1:]
            loss = vec[0] / vec[1]  # exact global masked mean (above)
            base = 2
            if cfg.num_experts is not None:
                loss = loss + moe_aux_coeff * vec[base]
                base += 1
            return loss, vec[base:]

        return loss_fn

    return init_fn, make_loss_fn, functools.partial(
        make_train_step, mesh, make_loss_fn, make_aux_loss_fn)


def make_train_step(mesh, make_loss_fn, make_aux_loss_fn, opt, param_specs,
                    scaler=None, grad_tap=None, collect_stats=False,
                    aux_stats=lambda aux: {"moe_aux": aux}):
    """``scaler=None``: the plain step.  With an ``amp`` scaler
    algorithm the unified non-finite sentinel
    (:mod:`apex_tpu.resilience.sentinel`) is threaded through: the
    loss is scaled, gradients overflow-checked (on the *global*
    grads, outside the shard_map — every rank sees the same flag),
    and the optimizer apply runs under one ``lax.cond`` so an
    overflow step leaves params and optimizer state bit-unchanged;
    ``sentinel.skipped_steps`` surfaces the skip count.  Signature
    becomes ``step(params, state, tokens, sentinel) -> (params,
    state, sentinel, loss)`` (loss reported unscaled).

    ``grad_tap`` (sentinel path only): a ``grads -> grads`` hook
    applied between the backward and the sentinel check — the seam
    the fault harness (:mod:`apex_tpu.testing.faults`) uses to
    inject non-finite gradients inside the compiled step.

    ``collect_stats`` appends a jit-carried
    :class:`apex_tpu.observability.PartialTrainStats` as the LAST
    output (loss, grad/param global-norm partials, non-finite leaf
    flags, loss scale, sentinel skip count, per-microbatch MoE aux).
    The params/grads here are SHARDED global arrays, so the norms
    leave the step as per-device partial sums
    (``ts.device_partial_norms`` — a shard_map whose output keeps
    the device axis, hence ZERO extra collectives; the host
    finalizes the tiny partials matrix at fetch time) and the aux
    vector rides the existing loss reductions
    (``make_aux_loss_fn``).  Zero host syncs; params and optimizer
    state stay bit-identical to the uninstrumented step (pinned by
    tests/test_observability.py).

    What :func:`build_gpt_3d` returns is this function closed over its
    first three arguments: the mesh and the builder's two loss makers
    (``make_aux_loss_fn(param_specs)`` gives ``(loss, aux)``, and
    ``aux_stats(aux)`` names the stats' fields ``aux`` fills).  The trainer
    of layers of more than one kind (:mod:`.hybrid_train`) closes it over
    its own."""
    from apex_tpu.observability import trainstats as ts

    loss_fn = (make_aux_loss_fn(param_specs) if collect_stats
               else make_loss_fn(param_specs))
    if collect_stats:
        partial_norms = ts.device_partial_norms(mesh, param_specs)

    if scaler is None:
        if not collect_stats:
            def step(params, state, tokens):
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, tokens)
                new_p, new_state = opt.step(grads, state, params)
                return new_p, new_state, loss

            return step

        def stats_step(params, state, tokens):
            (loss, aux_mb), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, tokens)
            new_p, new_state = opt.step(grads, state, params)
            stats = ts.partial_train_stats(
                loss, partial_norms(grads, params), **aux_stats(aux_mb))
            return new_p, new_state, loss, stats

        return stats_step

    from apex_tpu.resilience.sentinel import sentinel_guarded_apply

    def guarded_step(params, state, tokens, sent):
        scale_used = sent.scaler.scale

        if collect_stats:
            def scaled_loss(p, t):
                loss, aux_mb = loss_fn(p, t)
                return loss * scale_used, aux_mb

            (loss_s, aux_mb), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(params, tokens)
        else:
            def scaled_loss(p, t):
                return loss_fn(p, t) * scale_used

            loss_s, grads = jax.value_and_grad(scaled_loss)(
                params, tokens)
        if grad_tap is not None:
            grads = grad_tap(grads)
        # grads here are GLOBAL arrays (the shard_map lives inside
        # loss_fn), so no cross-rank flag agreement is needed:
        # axes=None.
        new_p, new_state, new_sent = sentinel_guarded_apply(
            scaler, opt, grads, state, params, sent,
            grad_scale=scale_used)
        loss = loss_s / scale_used
        if not collect_stats:
            return new_p, new_state, new_sent, loss
        stats = ts.partial_train_stats(
            loss, partial_norms(grads, params), grad_scale=scale_used,
            loss_scale=scale_used,
            skipped_steps=new_sent.skipped_steps, **aux_stats(aux_mb))
        return new_p, new_state, new_sent, loss, stats

    return guarded_step
