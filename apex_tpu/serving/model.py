"""Prefill/decode forward over the *training* transformer layers.

The serving twin of ``transformer.testing.gpt_parallel_train``: the same
parameter pytree (:class:`~apex_tpu.transformer.testing.gpt_parallel_train.
GPT3DParams`, layer stack flattened to ``[L, ...]``), the same
tensor-parallel modules (``ColumnParallelLinear``/``RowParallelLinear``
/``VocabParallelEmbedding``-backed :class:`Embedding`, ``ParallelMLP``,
``FusedLayerNorm``) and the same RoPE tables — but driven through two
inference-shaped entry points instead of a loss:

- :meth:`DecodeModel.prefill` — **batched chunked prefill**: a fixed
  ``[max_batch, chunk]`` slice of tokens, one chunk per slot, scattered
  into the paged arena at host-precomputed ``(block, offset)``
  destinations and attended with the chunked-prefill paged kernel
  (:func:`~apex_tpu.serving.paged_attention.paged_prefill_attention`):
  each token's per-token causal ``limit`` covers the request's whole
  cached context — prior chunks, shared prefix-cache blocks, and the
  in-chunk causal triangle — in ONE block sweep, which is what makes a
  long prompt sliceable across decode ticks (it never stalls a tick)
  and a prefix-cache hit a pure block-table entry.
- :meth:`DecodeModel.decode_step` — the jit-stable continuous-batching
  step: fixed ``[max_batch, spec_width]`` tokens (``spec_width = k + 1``
  with speculative decoding, 1 without — a compile-time constant of the
  engine config), per-slot positions/tables, an active mask and a
  per-slot ``n_draft``; inactive slots and unused draft positions are
  pure data (their cache writes are routed out of range and dropped;
  their attention limit is 0), so requests joining/leaving/preempting
  and per-tick draft counts anywhere in ``[0, k]`` never change a shape
  and the step **never recompiles**.

  With drafts the step is the **fused k+1 verify** (ISSUE 13): each
  slot's real last token plus its k drafted continuations attend in one
  multi-query block sweep with per-position causal limits
  (:func:`~apex_tpu.serving.paged_attention.paged_attention_decode`
  with 4-D q), every position samples with the request's policy at its
  own output index, and the accepted count — the longest prefix of
  drafts matching the step's own outputs — is computed in-graph.
  Accepted tokens are bitwise the tokens sequential decode would have
  produced (each verified position is teacher-forced on an accepted
  prefix), so speculation never changes a stream, only its arrival
  rate.  Rejected drafts cost nothing to undo: their K/V rows sit past
  the host-side length that was never advanced (the O(1) rollback —
  pointer/length moves, no copies), and the next tick overwrites them.

Both entry points **sample in-graph** (:mod:`.sampling`): per-slot
temperature/top-k/top-p/seed/step ride as ``[max_batch]`` data, the
vocab-sharded logits are gathered over tp before the draw, and the host
round-trips one int per slot per step, not a logits tensor.  Greedy
(``temperature == 0``) stays the exact argmax every token-identity
contract rests on.

With an **int8 cache** the K/V rows are quantized on write (one
symmetric fp32 scale per row, computed in-graph) and dequantized inside
the paged kernels — the arenas argument widens to
``(k, v, k_scales, v_scales)`` and everything else is unchanged.

Both entry points are **shard_map bodies**: run them under
``collectives.shard_over`` with the tensor axis bound (the engine does
this) — the parallel linears then shard exactly as in training, and
the K/V arena rows a rank touches are the heads it owns.

**Every operation belongs to a named layer** (ISSUE 35).  Both models put
their layers under :func:`~apex_tpu.observability.spans.named_span`, one
catalog for both: ``embed``, ``norm``, ``attn_proj`` (q/k/v/o and the latent
down- and up-projections; ``mla_absorb_q`` and ``mla_expand_o`` inside it),
``rope``, ``cache_write`` (the rows appended to the arenas, and where they
go), ``attention`` (the paged kernels' step plans and the glue round them;
a kernel itself keeps its own scope, ``paged_decode*`` / ``paged_prefill*``,
which names its Mosaic call), ``dense_ffn``, ``moe_router``,
``moe_experts``, ``moe_shared``, ``lm_head``, ``sample``, and
``layer_scan`` (what the uniform model's ``lax.scan`` itself costs: the
slices of the stacked weights and arenas and their way back).  Scopes are
metadata: the programs are the same instructions with and without them.
``spans.program_scopes()`` reads them back from the compiled programs.

**Layers of more than one kind** (ISSUE 27).  :class:`DecodeModel` above
is the case of one group: every layer alike, one ``lax.scan`` over the
stacked layers, one arena.  A configuration with a
:class:`~apex_tpu.transformer.testing.standalone_transformer_lm.HybridSpec`
(``TransformerConfig.hybrid``) is served by :class:`HybridDecodeModel`,
the same two entry points over a **walk of the layers in their published
order**: each layer names its attention kind (full or sliding-window, with
its own head counts, q/k width beside v width, rotary base and optional
sink logits) and its feed-forward (the dense SwiGLU or the top-k expert
layer of :func:`apex_tpu.transformer.moe.held_experts_ffn`, which is told
which experts this process holds).  Each attention kind is a cache group
(:class:`~apex_tpu.serving.kv_cache.CacheGroup`) with its own block table,
and each layer owns its ``(k, v)`` arena pair whole, so the walk is
unrolled: a layer's kernel call takes its arena as it lies and its
appended rows land in place in the donated buffer.  The blocks are
RMSNorm, bias-free, with every GEMM's operands, the activations between
them and the cache in the model's dtype (bf16) and the residual stream,
the norm statistics, the accumulation, the router and the logits in fp32;
tokens are flattened to ``[tokens, hidden]``.

**A latent kind** (ISSUE 33; multi-head latent attention) caches one row a
token and layer, the normed latent beside the one rotated key all heads
share, and is computed in the **absorbed** form: each head's no-position
query is multiplied through ``W_UK`` into the latent's channels (scope
``mla_absorb_q``), the paged kernel scores ``[q~ | q_rope]`` against the
cached rows as they lie and takes the rows' latent channels as values, and
``W_UV`` expands each head's result afterwards (scope ``mla_expand_o``).
Nothing expanded is ever stored.  A prefill call's absorbed queries are
``tokens x heads x row`` (a gigabyte at 8,192 tokens and 128 heads), so
the attention of a call is walked a few slots at a time.
**A sparse latent kind** (ISSUE 37; learned sparse attention, GLM-5's)
adds an indexer to a latent kind: its keys are cached beside the latent rows
in a second arena under the same table, and each query attends only the
``index_topk`` tokens its indexer scores highest (scopes ``dsa_index``,
``dsa_select``, ``sparse_latent``; :mod:`.sparse_attention`), at decode and
prefill alike; the decode program returns the positions it attended.
:func:`decode_model` picks the class from the configuration;
:func:`serving_config` no longer refuses experts, only the Switch layer,
which remains a training dry run.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.parallel import collectives as cc
from apex_tpu.serving.fused_ops import (
    fused_residual_norm,
    residual_norm_unfused,
)
from apex_tpu.normalization.fused_layer_norm import fused_rms_norm_affine
from apex_tpu.observability.spans import named_span
from apex_tpu.serving.kv_cache import KVCacheConfig
from apex_tpu.serving.lora import LoRAConfig, lora_delta
from apex_tpu.serving.paged_attention import (
    paged_attention_decode,
    paged_attention_decode_unfused,
    paged_decode_latent,
    paged_decode_latent_unfused,
    paged_prefill_attention,
    paged_prefill_attention_unfused,
    paged_prefill_latent,
    paged_prefill_latent_unfused,
)
from apex_tpu.serving.sampling import sample_tokens
from apex_tpu.serving.sparse_attention import (
    gather_rows,
    index_weights_scale,
    paged_index_scores,
    paged_index_scores_unfused,
    select_topk,
    select_topk_mask,
    sparse_latent_attention,
)
from apex_tpu.transformer.layers.layer_norm import FusedLayerNorm
from apex_tpu.transformer import moe
from apex_tpu.transformer.rope import (
    apply_rotary_decode,
    apply_rotary_interleaved,
    apply_rotary_packed,
    rotary_cos_sin,
)
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
)
from apex_tpu.transformer.tensor_parallel.utils import divide
from apex_tpu.transformer.testing.standalone_transformer_lm import (
    Embedding,
    HybridParams,
    ParallelMLP,
    TransformerConfig,
    parallel_lm_logits,
)

__all__ = ["DecodeModel", "HybridDecodeModel", "HybridParams",
           "decode_model", "serving_config"]


def serving_config(config: TransformerConfig) -> TransformerConfig:
    """The inference view of a training config.

    Dropout off (inference), sequence parallelism off (a decode step
    has one token per slot — there is no sequence dim to shard; param
    shapes are identical so training checkpoints load unchanged),
    ring overlap off (no SP collective to decompose), fp8 off (the
    delayed-scaling state lives in a training-side collection).
    """
    if config.apply_residual_connection_post_layernorm:
        raise NotImplementedError(
            "serving decode assumes the standard pre-LN residual; "
            "apply_residual_connection_post_layernorm is not wired")
    if config.num_experts is not None:
        raise NotImplementedError(
            "the Switch top-1 layer (num_experts) is a training dry run; "
            "serving takes its experts from TransformerConfig.hybrid")
    return dataclasses.replace(
        config, hidden_dropout=0.0, attention_dropout=0.0,
        sequence_parallel=False, overlap_comm=False, context_axis=None,
        fp8=False)


def _quantize_rows(x):
    """Symmetric int8 row quantization: ``x [..., d]`` -> (int8 values,
    fp32 per-row scales ``[...]``).  ``amax / 127`` with an epsilon
    floor so an all-zero row round-trips to exact zeros."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scales = jnp.maximum(amax / 127.0, 1e-8).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scales[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scales


class DecodeModel:
    """Functional prefill/decode forward bound to a config + cache shape.

    Stateless: parameters and cache arenas are arguments, so the same
    instance serves any checkpoint of the architecture and the engine
    can donate the arenas through jit.
    """

    def __init__(self, config: TransformerConfig, cache: KVCacheConfig, *,
                 fused_attention: bool = True, fuse_epilogue: bool = True,
                 lora: Optional[LoRAConfig] = None):
        cfg = serving_config(config)
        self.cfg = cfg
        self.cache = cache
        self.fused_attention = fused_attention
        self.fuse_epilogue = fuse_epilogue
        self.lora = lora

        d = cfg.head_dim
        n, g = cfg.num_attention_heads, cfg.query_groups
        self.hpg = divide(n, g)
        if cache.kv_heads != g:
            raise ValueError(
                f"cache kv_heads ({cache.kv_heads}) != model query_groups "
                f"({g})")
        if cache.head_dim != d:
            raise ValueError(
                f"cache head_dim ({cache.head_dim}) != model head_dim ({d})")
        self.embed = Embedding(cfg)
        self.qkv = ColumnParallelLinear(
            cfg.hidden_size, (n + 2 * g) * d, axis=cfg.tensor_axis,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        self.dense = RowParallelLinear(
            n * d, cfg.hidden_size, input_is_parallel=True,
            skip_bias_add=True, axis=cfg.tensor_axis,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        self.mlp = ParallelMLP(cfg)
        self.ln = FusedLayerNorm(cfg.hidden_size, eps=cfg.layernorm_epsilon)
        if lora is not None:
            # the adapter path needs the MLP's two GEMMs exposed (the
            # fc1 delta lands before the activation), so bind the same
            # parallel linears ParallelMLP builds, under its param
            # names — _mlp_with_adapter replays its ops verbatim
            self.mlp_fc1 = ColumnParallelLinear(
                cfg.hidden_size, cfg.ffn_size,
                sequence_parallel=cfg.sequence_parallel,
                skip_bias_add=True, axis=cfg.tensor_axis,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                fp8=cfg.fp8, overlap_comm=cfg.overlap_comm)
            self.mlp_gate = None
            if cfg.swiglu:
                self.mlp_gate = ColumnParallelLinear(
                    cfg.hidden_size, cfg.ffn_size,
                    sequence_parallel=cfg.sequence_parallel,
                    skip_bias_add=True, axis=cfg.tensor_axis,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    fp8=cfg.fp8, overlap_comm=cfg.overlap_comm)
            self.mlp_fc2 = RowParallelLinear(
                cfg.ffn_size, cfg.hidden_size, input_is_parallel=True,
                sequence_parallel=cfg.sequence_parallel,
                skip_bias_add=True, axis=cfg.tensor_axis,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                fp8=cfg.fp8, overlap_comm=cfg.overlap_comm)

    # ----------------------------------------------------------------- util

    def _split_qkv(self, qkv):
        """Group-major fused-QKV split (``ParallelAttention`` layout):
        per K/V group its query heads, then its one K and one V head."""
        cfg = self.cfg
        d = cfg.head_dim
        world = cc.bound_axis_size(cfg.tensor_axis)
        g_local = divide(cfg.query_groups, world)
        n_local = divide(cfg.num_attention_heads, world)
        s, b = qkv.shape[0], qkv.shape[1]
        qkv = qkv.reshape(s, b, g_local, (self.hpg + 2) * d)
        q = qkv[..., :self.hpg * d].reshape(s, b, n_local, d)
        k = qkv[..., self.hpg * d:(self.hpg + 1) * d]
        v = qkv[..., (self.hpg + 1) * d:]
        return q, k, v

    def _append_rows(self, layer_arenas, dest_blocks, dest_offsets, k, v):
        """Scatter K/V rows into one layer's arena slice at
        ``(block, offset)`` destinations (out-of-range = dropped —
        inactive slots and padding route there), quantizing on write
        for the int8 cache (the per-row scales land beside the rows,
        through the same dropped-scatter indices)."""
        if self.cache.quantized:
            k_layer, v_layer, ks_layer, vs_layer = layer_arenas
            qk, sk = _quantize_rows(k)
            qv, sv = _quantize_rows(v)
            k_layer = k_layer.at[dest_blocks, dest_offsets].set(
                qk, mode="drop")
            v_layer = v_layer.at[dest_blocks, dest_offsets].set(
                qv, mode="drop")
            ks_layer = ks_layer.at[dest_blocks, dest_offsets].set(
                sk, mode="drop")
            vs_layer = vs_layer.at[dest_blocks, dest_offsets].set(
                sv, mode="drop")
            return (k_layer, v_layer, ks_layer, vs_layer)
        k_layer, v_layer = layer_arenas
        k_layer = k_layer.at[dest_blocks, dest_offsets].set(
            k.astype(k_layer.dtype), mode="drop")
        v_layer = v_layer.at[dest_blocks, dest_offsets].set(
            v.astype(v_layer.dtype), mode="drop")
        return (k_layer, v_layer)

    def _attend_kwargs(self, layer_arenas):
        """(k, v[, scale kwargs]) of one layer slice for the kernels."""
        if self.cache.quantized:
            k_layer, v_layer, ks_layer, vs_layer = layer_arenas
            return (k_layer, v_layer), dict(k_scales=ks_layer,
                                            v_scales=vs_layer)
        return layer_arenas, {}

    def _lora_delta(self, x, a, b, slots):
        """The gathered rank-r bypass of one projection for every batch
        slot (``slots [max_batch]`` is DATA — see :mod:`.lora`)."""
        return lora_delta(x, a, b, slots, fused=self.lora.fused)

    def _lora_psum(self, d):
        """Sum a row-parallel projection's partial deltas over tp (A is
        sharded on the input dim there, so each rank holds a partial —
        the one collective the adapter path adds)."""
        cfg = self.cfg
        if cfg.tensor_axis is not None \
                and cc.bound_axis_size(cfg.tensor_axis) > 1:
            return cc.all_reduce(d, cfg.tensor_axis)
        return d

    def _mlp_with_adapter(self, mlp_params, x, fc1_a, fc1_b, fc2_a, fc2_b,
                          slots):
        """``ParallelMLP`` replayed op-for-op with the gathered adapter
        deltas injected: fc1's (column-parallel — lands pre-split like
        the base output, before the activation) and fc2's (row-parallel
        — per-rank partial, psum'd).  Zero-slot gathers add exact zeros,
        keeping the bare stream bitwise."""
        cfg = self.cfg
        h, bias = self.mlp_fc1.apply(
            {"params": mlp_params["dense_h_to_4h"]}, x)
        h = h + bias + self._lora_delta(x, fc1_a, fc1_b, slots)
        if cfg.swiglu:
            gate, gate_bias = self.mlp_gate.apply(
                {"params": mlp_params["dense_h_to_4h_gate"]}, x)
            h = jax.nn.silu(gate + gate_bias) * h
        else:
            h = jax.nn.gelu(h, approximate=cfg.bias_gelu_fusion)
        out, out_bias = self.mlp_fc2.apply(
            {"params": mlp_params["dense_4h_to_h"]}, h)
        out = out + self._lora_psum(
            self._lora_delta(h, fc2_a, fc2_b, slots))
        return out, out_bias

    def _layer_stack(self, params, x, arenas, attn_core, adapters=None,
                     adapter_slots=None):
        """Scan the ``[L, ...]`` layer stack; each step consumes its own
        arena slices and emits the updated ones (the scan re-stacks
        them, which XLA aliases into the donated input arenas).

        With ``adapters`` (the 8 ``[L, n_slots, ...]`` LoRA arrays,
        threaded exactly like the arenas so the engine can donate them
        too), every projection adds its slot-gathered delta; the scan
        re-emits the adapter slices unchanged."""
        n_ar = len(arenas)

        def body(carry, xs):
            x = carry
            lp, rest = xs[0], xs[1:]
            layer_arenas = rest[:n_ar]
            layer_adapters = rest[n_ar:]
            with named_span("norm"):
                ln1 = self.ln.apply({"params": lp["input_layernorm"]}, x)
            with named_span("attn_proj"):
                qkv = self.qkv.apply(
                    {"params": lp["self_attention"]["query_key_value"]}, ln1)
                if layer_adapters:
                    (qkv_a, qkv_b, dense_a, dense_b,
                     fc1_a, fc1_b, fc2_a, fc2_b) = layer_adapters
                    qkv = qkv + self._lora_delta(ln1, qkv_a, qkv_b,
                                                 adapter_slots)
                q, k, v = self._split_qkv(qkv)
            ctx, layer_arenas = attn_core(q, k, v, layer_arenas)
            with named_span("attn_proj"):
                y, y_bias = self.dense.apply(
                    {"params": lp["self_attention"]["dense"]}, ctx)
                if layer_adapters:
                    y = y + self._lora_psum(self._lora_delta(
                        ctx, dense_a, dense_b, adapter_slots))
            ln2 = lp["post_attention_layernorm"]
            with named_span("norm"):
                if self.fuse_epilogue:
                    ln2_out, h = fused_residual_norm(
                        y, x, ln2["scale"], ln2["bias"], bias=y_bias,
                        eps=self.cfg.layernorm_epsilon)
                else:
                    ln2_out, h = residual_norm_unfused(
                        y, x, ln2["scale"], ln2["bias"], bias=y_bias,
                        eps=self.cfg.layernorm_epsilon)
            with named_span("dense_ffn"):
                if layer_adapters:
                    m, m_bias = self._mlp_with_adapter(
                        lp["mlp"], ln2_out, fc1_a, fc1_b, fc2_a, fc2_b,
                        adapter_slots)
                else:
                    m, m_bias = self.mlp.apply({"params": lp["mlp"]},
                                               ln2_out)
                out = h + m + m_bias
            return out, layer_arenas + tuple(layer_adapters)

        xs = (params.layers,) + tuple(arenas)
        if adapters is not None:
            xs = xs + tuple(adapters)
        with named_span("layer_scan"):
            x, out = lax.scan(body, x, xs)
        if adapters is None:
            return x, out, None
        return x, out[:n_ar], out[n_ar:]

    def _head(self, params, x):
        """Final LN + tied LM head, vocab gathered over tp.

        Returns ``logits [s, b, vocab]`` with the FULL vocab (gathered
        so the in-graph sampler — and the host — see one consistent id
        space)."""
        cfg = self.cfg
        with named_span("norm"):
            hidden = self.ln.apply({"params": params.final_ln}, x)
        with named_span("lm_head"):
            logits = parallel_lm_logits(
                hidden, params.embedding["word_embeddings"]["embedding"],
                cfg)
            if cfg.tensor_axis is not None \
                    and cc.bound_axis_size(cfg.tensor_axis) > 1:
                logits = cc.all_gather(logits, cfg.tensor_axis,
                                       concat_axis=-1)
        return logits

    def _rope_tables(self, positions, dtype):
        cfg = self.cfg
        if cfg.position_embedding_type != "rope":
            return None
        with named_span("rope"):
            return rotary_cos_sin(positions, cfg.rotary_dim,
                                  cfg.rotary_base, dtype)

    def _embed(self, params, tokens, position_ids):
        """``[s, b, hidden]`` of ``tokens [b, s]``."""
        with named_span("embed"):
            if self.cfg.position_embedding_type == "learned":
                return self.embed.apply({"params": params.embedding},
                                        tokens, position_ids)
            return self.embed.apply({"params": params.embedding}, tokens)

    # ---------------------------------------------------------------- entry

    def decode_step(self, arenas, params, tokens, positions, block_tables,
                    active, n_draft, temperature, top_k, top_p, seeds,
                    steps, adapters=None, adapter_slots=None):
        """One continuously-batched decode/verify step (shard_map body).

        ``arenas`` — ``(k, v)`` or ``(k, v, k_scales, v_scales)``;
        ``tokens [max_batch, S]`` where ``S = spec_width`` (column 0 is
        each slot's last sampled/prompt token, columns ``1..n_draft``
        its drafted continuations, the rest padding), ``positions
        [max_batch]`` (the cache index column 0 is written at — the
        slot's current length), ``block_tables
        [max_batch, max_blocks]``, ``active [max_batch]`` bool,
        ``n_draft [max_batch]`` (0..S-1, per-slot draft count — DATA),
        and the ``[max_batch]`` sampling-policy arrays (:mod:`.sampling`
        — ``steps`` is each slot's output-token counter, the seed
        fold-in; verify position t draws at counter ``steps + t``).
        Every shape is fixed by the engine config; request churn,
        preemption, eviction, draft counts and policy changes only move
        values.  Returns ``(arenas, out_tokens [max_batch, S],
        accepted [max_batch], logits [max_batch, S, vocab])`` —
        ``accepted`` is the longest prefix of drafts matching the
        step's own outputs, so the host emits ``out_tokens[:, :a + 1]``
        and advances lengths by ``a + 1`` (rejection is a length that
        simply never advances — nothing to copy back).

        With LoRA enabled the step also takes ``adapters`` (the 8
        donated arena arrays) and ``adapter_slots [max_batch]`` (each
        slot's arena row — DATA, like the block tables), and returns
        ``(arenas, adapters, out, accepted, logits)``.
        """
        cfg = self.cfg
        cache = self.cache
        bs = cache.block_size
        B, S = tokens.shape
        positions = positions.astype(jnp.int32)
        n_draft = n_draft.astype(jnp.int32)
        offsets = lax.broadcasted_iota(jnp.int32, (B, S), 1)
        pos_ids = positions[:, None] + offsets          # [B, S]
        live = active[:, None] & (offsets <= n_draft[:, None])
        with named_span("attention"):
            # per-position causal horizon: verify token t sees cache
            # positions < pos + t + 1 (its own row included — scattered
            # below, before the attention, the prefill convention)
            limits = jnp.where(live, pos_ids + 1, 0).astype(jnp.int32)
            lengths = jnp.where(active, positions + n_draft + 1,
                                0).astype(jnp.int32)
        with named_span("cache_write"):
            # cache write destinations; inactive slots and padding columns
            # write out of range and the scatter drops them
            logical = jnp.clip(pos_ids // bs, 0, block_tables.shape[1] - 1)
            phys = jnp.take_along_axis(block_tables, logical, axis=1)
            dest_blocks = jnp.where(live, phys,
                                    cache.n_blocks).astype(jnp.int32)
            dest_offsets = (pos_ids % bs).astype(jnp.int32)

        x = self._embed(params, tokens, pos_ids)   # [S, max_batch, hidden]
        rope = None
        if cfg.position_embedding_type == "rope":
            if S == 1:
                rope = self._rope_tables(positions, x.dtype)
            else:
                cos, sin = self._rope_tables(pos_ids.reshape(-1), x.dtype)
                with named_span("rope"):
                    rope = (cos.reshape(B, S, -1).transpose(1, 0, 2),
                            sin.reshape(B, S, -1).transpose(1, 0, 2))

        attend = (paged_attention_decode if self.fused_attention
                  else paged_attention_decode_unfused)

        def attn_core(q, k, v, layer_arenas):
            # q [S, B, n_local, d]; k/v [S, B, g_local, d]
            if rope is not None:
                cos, sin = rope
                rot = apply_rotary_decode if S == 1 else apply_rotary_packed
                with named_span("rope"):
                    q = rot(q, cos, sin)
                    k = rot(k, cos, sin)
            # append the K/V rows, then attend over the paged cache
            with named_span("cache_write"):
                layer_arenas = self._append_rows(
                    layer_arenas, dest_blocks, dest_offsets,
                    k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3))
            kv, sc = self._attend_kwargs(layer_arenas)
            with named_span("attention"):
                if S == 1:
                    # the single-token kernel: the non-speculative engine
                    # keeps exactly the PR 8 decode program
                    ctx = attend(q[0], *kv, block_tables, lengths, **sc)
                else:
                    ctx = attend(q.transpose(1, 0, 2, 3), *kv,
                                 block_tables, lengths, limits=limits,
                                 **sc)                      # [B, S, n, d]
                    ctx = ctx.transpose(1, 0, 2, 3)
                return (ctx.reshape(S, B, -1).astype(q.dtype),
                        layer_arenas)

        x, arenas, adapters = self._layer_stack(
            params, x, arenas, attn_core, adapters, adapter_slots)
        logits = self._head(params, x)             # [S, B, vocab]
        with named_span("lm_head"):
            logits = logits.transpose(1, 0, 2)     # [B, S, vocab]
        # every position samples with its slot's policy at its own
        # output counter — accepted draws are the draws the sequential
        # path would have made (same key, same teacher-forced logits)
        rep = lambda a: jnp.repeat(a, S, axis=0)   # noqa: E731
        with named_span("sample"):
            sampled = sample_tokens(
                logits.reshape(B * S, -1), rep(temperature), rep(top_k),
                rep(top_p), rep(seeds),
                (steps[:, None] + offsets).reshape(-1))
            out = jnp.where(live, sampled.reshape(B, S),
                            0).astype(jnp.int32)
            if S > 1:
                # accepted = longest prefix with draft t == output t-1
                match = (tokens[:, 1:].astype(jnp.int32) == out[:, :-1]) \
                    & (offsets[:, 1:] <= n_draft[:, None])
                accepted = jnp.cumprod(
                    match.astype(jnp.int32), axis=1).sum(axis=1)
            else:
                accepted = jnp.zeros((B,), jnp.int32)
            accepted = jnp.where(active, accepted, 0).astype(jnp.int32)
        if adapters is not None:
            return arenas, adapters, out, accepted, logits
        return arenas, out, accepted, logits

    def prefill(self, arenas, params, tokens, position_ids, block_tables,
                lengths, limits, dest_blocks, dest_offsets, sample_index,
                temperature, top_k, top_p, seeds, steps, adapters=None,
                adapter_slots=None):
        """Batched chunked prefill of one ``[max_batch, chunk]`` slice
        (shard_map body).

        Per slot: ``tokens``/``position_ids [max_batch, chunk]`` — this
        tick's slice of the slot's prompt at its *absolute* positions
        (also the RoPE angle source, so chunking composes with rope);
        ``dest_blocks``/``dest_offsets [max_batch, chunk]`` — each
        token's physical cache destination (out-of-range = dropped,
        used for padding); ``block_tables [max_batch, max_blocks]`` and
        ``lengths [max_batch]`` — the slot's table and its total cache
        length INCLUDING this chunk; ``limits [max_batch, chunk]`` —
        per-token causal horizons (0 = padding).  Shared prefix-cache
        blocks and earlier chunks need no special path: they are table
        entries the per-token limits already reach.

        ``sample_index [max_batch]`` — for slots whose prompt completes
        this chunk, the in-chunk index of the last prompt token; the
        logits there are sampled with the slot's policy arrays (the
        request's FIRST generated token).  Out-of-range = no sample.
        Returns ``(arenas, next_tokens [max_batch],
        logits [max_batch, chunk, vocab])`` — with LoRA enabled,
        ``adapters``/``adapter_slots`` join exactly as in
        :meth:`decode_step` and the adapters return between the arenas
        and the tokens.
        """
        cfg = self.cfg
        B, T = tokens.shape
        dest_blocks = dest_blocks.astype(jnp.int32)
        dest_offsets = dest_offsets.astype(jnp.int32)

        x = self._embed(params, tokens, position_ids)
        # x: [chunk, max_batch, hidden]
        rope = None
        if cfg.position_embedding_type == "rope":
            cos, sin = self._rope_tables(
                position_ids.reshape(-1), x.dtype)
            with named_span("rope"):
                rope = (cos.reshape(B, T, -1).transpose(1, 0, 2),
                        sin.reshape(B, T, -1).transpose(1, 0, 2))

        attend = (paged_prefill_attention if self.fused_attention
                  else paged_prefill_attention_unfused)

        def attn_core(q, k, v, layer_arenas):
            # q [T, B, n_local, d]; k/v [T, B, g_local, d] (compact GQA)
            if rope is not None:
                cos, sin = rope
                with named_span("rope"):
                    q = apply_rotary_packed(q, cos, sin)
                    k = apply_rotary_packed(k, cos, sin)
            with named_span("cache_write"):
                layer_arenas = self._append_rows(
                    layer_arenas, dest_blocks, dest_offsets,
                    k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3))
            kv, sc = self._attend_kwargs(layer_arenas)
            with named_span("attention"):
                ctx = attend(q.transpose(1, 0, 2, 3), *kv, block_tables,
                             lengths, limits, **sc)   # [B, T, n, d]
                return (ctx.transpose(1, 0, 2, 3).reshape(T, B, -1)
                        .astype(q.dtype), layer_arenas)

        x, arenas, adapters = self._layer_stack(
            params, x, arenas, attn_core, adapters, adapter_slots)
        logits = self._head(params, x)             # [T, B, vocab]
        with named_span("lm_head"):
            logits = logits.transpose(1, 0, 2)     # [B, T, vocab]
        with named_span("sample"):
            idx = jnp.clip(sample_index.astype(jnp.int32), 0, T - 1)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]   # [B, vocab]
            sampled = sample_tokens(last, temperature, top_k, top_p,
                                    seeds, steps)
            valid = (sample_index.astype(jnp.int32) >= 0) & \
                (sample_index.astype(jnp.int32) < T)
            next_tokens = jnp.where(valid, sampled, 0).astype(jnp.int32)
        if adapters is not None:
            return arenas, adapters, next_tokens, logits
        return arenas, next_tokens, logits


# ------------------------------------------------ layers of several kinds


def unserved_fields(spec) -> list:
    """The fields of a :class:`HybridSpec` that ask for what
    :class:`HybridDecodeModel` does not implement yet (the trainer does),
    by name: it refuses them instead of serving another model."""
    out = []
    for kind in spec.kinds:
        out += [f"{kind.name}.{name}" for name, used in (
            ("rotary_dim=0", kind.rotary_dim == 0),
            ("qk_norm", kind.qk_norm), ("gate", kind.gate)) if used]
    out += [name for name, used in (
        ("sandwich_norm", spec.sandwich_norm),
        ("embedding_multiplier", spec.embedding_multiplier != 1.0)) if used]
    if spec.experts is not None and spec.experts.route_eps != 0.0:
        out.append("experts.route_eps")
    return out


# the absorbed queries of one walk of a latent prefill call's slots, at most
_LATENT_WALK_BYTES = 256 << 20


def _slots_a_walk(slots: int, bytes_a_slot: int) -> int:
    """The most slots, a divisor of ``slots``, whose absorbed queries lie
    within ``_LATENT_WALK_BYTES``."""
    walk = max(1, min(slots, _LATENT_WALK_BYTES // max(bytes_a_slot, 1)))
    while slots % walk:
        walk -= 1
    return walk


def decode_model(config: TransformerConfig, cache: KVCacheConfig, **kwargs):
    """The serving forward of ``config``: :class:`HybridDecodeModel` where
    it describes its layers one by one, else :class:`DecodeModel`."""
    if config.hybrid is not None:
        return HybridDecodeModel(config, cache, **kwargs)
    return DecodeModel(config, cache, **kwargs)


class HybridDecodeModel:
    """Prefill/decode forward over layers of more than one kind (module
    docstring).  Stateless like :class:`DecodeModel`; ``arenas`` is
    ``init_group_arenas``'s tree (per group, per layer, ``(k, v)``) and
    ``block_tables`` a tuple, one table per cache group."""

    def __init__(self, config: TransformerConfig, cache: KVCacheConfig, *,
                 fused_attention: bool = True, fuse_epilogue: bool = True,
                 lora: Optional[LoRAConfig] = None):
        del fuse_epilogue       # the LayerNorm epilogue kernel: not these
        cfg = serving_config(config)
        if lora is not None:
            raise NotImplementedError("no LoRA over hybrid layers yet")
        if cfg.tensor_axis is not None \
                and cc.bound_axis_size(cfg.tensor_axis) > 1:
            raise NotImplementedError(
                "hybrid layers are served on one chip's share: no tp yet")
        unserved = unserved_fields(cfg.hybrid)
        if unserved:
            raise NotImplementedError(
                "serving does not implement these HybridSpec fields yet "
                f"(the trainer does): {', '.join(unserved)}")
        self.cfg, self.cache = cfg, cache
        self.spec = cfg.hybrid
        self.fused_attention = fused_attention
        # layer -> (cache group, index among the group's layers)
        self.place = {}
        for gi, group in enumerate(cache.groups):
            kind = self.spec.kinds[self.spec.layer_kinds[group.layers[0]]]
            if (group.kv_heads, group.k_dim, group.v_dim, group.latent,
                    group.window, group.index_dim) != kind.cache_row + (
                        kind.window, kind.index_row):
                raise ValueError(
                    f"cache group {gi} {group} does not hold the rows of "
                    f"attention kind {kind} (a latent kind keeps one row "
                    "of latent_rank + rotary_dim channels, any other k_dim "
                    "beside v_dim a KV head)")
            for li, layer in enumerate(group.layers):
                self.place[layer] = (gi, li)
        if sorted(self.place) != list(range(cfg.num_layers)):
            raise ValueError("the cache groups do not cover the layers")
        self.n_expert_layers = sum(self.spec.layer_experts)
        self.sparse = any(self.spec.kinds[k].sparse
                          for k in self.spec.layer_kinds)

    # ----------------------------------------------------------------- util

    def _norm(self, x, weight):
        return fused_rms_norm_affine(x, weight, weight.shape[-1],
                                     self.cfg.layernorm_epsilon)

    def _mm(self, x, w):
        return jnp.dot(x, w, preferred_element_type=jnp.float32)

    def _rotate(self, x, positions, kind):
        """Half rotation of the leading ``rotary_dim`` channels of
        ``x [tokens, heads, k_dim]`` at ``positions [tokens]``, in fp32."""
        cos, sin = rotary_cos_sin(positions, kind.rotary_dim,
                                  kind.rotary_base, jnp.float32)
        return apply_rotary_packed(x.astype(jnp.float32)[:, None],
                                   cos[:, None], sin[:, None])[:, 0]

    def _latent_rows(self, lp, h1, positions, kind, lanes):
        """A latent layer's projections of normed ``h1 [tokens, hidden]``:
        ``(q_nope [tokens, heads, nope_dim], q_rope [tokens, heads,
        rotary_dim], rows [tokens, lanes], index)`` in the model's dtype;
        ``rows`` are what the cache keeps, ``[normed latent | rotated key]``
        and zeros up to ``lanes``.  Both low-rank vectors are RMS-normed,
        the rotation (YaRN-scaled where the kind says) is in fp32.
        ``index`` is the indexer's :meth:`_index_rows` where the kind is
        sparse, else ``None``."""
        dtype = self.cfg.dtype
        rank, nope = kind.latent_rank, kind.nope_dim
        with named_span("attn_proj"):
            c_q = self._mm(h1, lp["wq_a"])
        with named_span("norm"):
            c_q = self._norm(c_q, lp["q_a_norm"])
        with named_span("attn_proj"):
            q = self._mm(c_q.astype(dtype), lp["wq_b"]).reshape(
                -1, kind.num_heads, kind.k_dim)
            c_kv = self._mm(h1, lp["wkv_a"])
        with named_span("rope"):
            cos, sin = rotary_cos_sin(positions, kind.rotary_dim,
                                      kind.rotary_base, jnp.float32,
                                      scaling=kind.rotary_scaling)
            q_rope = apply_rotary_interleaved(q[..., nope:], cos, sin)
            k_rope = apply_rotary_interleaved(c_kv[:, None, rank:], cos,
                                              sin)
        with named_span("norm"):
            latent = self._norm(c_kv[:, :rank], lp["kv_a_norm"])
        with named_span("cache_write"):
            rows = jnp.concatenate(
                [latent, k_rope[:, 0],
                 jnp.zeros((c_kv.shape[0], lanes - c_kv.shape[1]),
                           jnp.float32)], axis=-1).astype(dtype)
        with named_span("attn_proj"):
            q_nope = q[..., :nope].astype(dtype)
        index = (self._index_rows(lp, h1, c_q.astype(dtype), positions,
                                  kind) if kind.sparse else None)
        return q_nope, q_rope.astype(dtype), rows, index

    def _index_rows(self, lp, h1, c_q, positions, kind):
        """The indexer's projections (a sparse kind): ``(q [tokens,
        index_heads, index_dim], w [tokens, index_heads], k [tokens,
        index_dim])``.  ``q`` from the normed compressed query ``c_q``,
        ``k`` a LayerNorm (gain and bias) of a projection of ``h1``, their
        leading ``index_rope_dim`` channels rotated (channel ``2 t`` with
        ``2 t + 1``) in fp32, both in the model's dtype; ``w`` the heads'
        weights in fp32, scaled by ``(heads * dim) ** -0.5``."""
        dtype = self.cfg.dtype
        r = kind.index_rope_dim
        with named_span("attn_proj"):
            q = self._mm(c_q, lp["wq_idx"]).reshape(
                -1, kind.index_heads, kind.index_dim)
            k = self._mm(h1, lp["wk_idx"])
            w = self._mm(h1, lp["w_idx"]) * index_weights_scale(
                kind.index_heads, kind.index_dim)
        with named_span("norm"):
            k = self._index_norm(k, lp, kind)
        with named_span("rope"):
            q = self._index_rope(q, positions, r, kind).astype(dtype)
            k = self._index_rope(k[:, None], positions, r, kind)[:, 0]
        return q, w, k.astype(dtype)

    @staticmethod
    def _index_norm(k, lp, kind):
        """The index key's LayerNorm (gain and bias) in fp32."""
        mean = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        return ((k - mean) * lax.rsqrt(var + kind.index_eps)
                * lp["k_idx_norm"].astype(jnp.float32)
                + lp["k_idx_norm_bias"].astype(jnp.float32))

    @staticmethod
    def _index_rope(x, positions, r, kind):
        """Rotation of the leading ``r`` channels of ``x [tokens, heads,
        d]`` (pairs ``2 t``, ``2 t + 1``), base ``rotary_base``, in fp32."""
        cos, sin = rotary_cos_sin(positions, r, kind.rotary_base,
                                  jnp.float32)
        return jnp.concatenate(
            [apply_rotary_interleaved(x[..., :r], cos, sin), x[..., r:]],
            axis=-1)

    def _sparse(self, kind, q, index, arena, keys, table, limits):
        """Sparse absorbed attention of ``q [slots, Q, heads, lanes]``:
        the indexer's scores against the cached index ``keys`` (scope
        ``dsa_index``), each query's top ``index_topk`` tokens (scope
        ``dsa_select``), and the absorbed attention over those rows of the
        latent ``arena`` (scope ``sparse_latent``).  ``index = (q [slots,
        Q, index_heads, index_dim], w [slots, Q, index_heads])``; ``limits
        [slots, Q]`` each query's causal horizon.  Returns ``(out [slots,
        Q, heads, latent_rank], selected [slots, Q, index_topk])``, the
        selected positions, ``-1`` where no token."""
        scores_of = (paged_index_scores if self.fused_attention
                     else paged_index_scores_unfused)
        with named_span("dsa_index"):
            scores = scores_of(index[0], index[1], keys, table, limits)
        with named_span("dsa_select"):
            positions, live = select_topk(scores, kind.index_topk)
        with named_span("sparse_latent"):
            rows = gather_rows(arena, table, positions)
            out = sparse_latent_attention(
                q, rows, live, v_dim=kind.latent_rank,
                scale=self._latent_scale(kind))
            return out, jnp.where(live, positions, -1)

    def _prefill_selection(self, kind, index, keys, table, limits):
        """A prefill walk's selection ``[slots, chunk, max_seq]`` int8: the
        indexer's scores (scope ``dsa_index``) and each token's top
        ``index_topk`` as a mask (scope ``dsa_select``), which the latent
        prefill kernel takes; a chunk's queries share most of their keys,
        so the kernel visits every key once and masks, where gathering
        each query's rows would read ``chunk x index_topk`` rows a slot."""
        scores_of = (paged_index_scores if self.fused_attention
                     else paged_index_scores_unfused)
        with named_span("dsa_index"):
            scores = scores_of(index[0], index[1], keys, table, limits)
        with named_span("dsa_select"):
            return select_topk_mask(scores, kind.index_topk)

    def _latent_attention(self, kind, lp, q_nope, q_rope, lanes, kernel):
        """The absorbed form round ``kernel(q [..., heads, lanes]) ->
        [..., heads, latent_rank]``: ``W_UK`` into the query before it,
        ``W_UV`` after.  Returns ``[..., heads * v_dim]`` in the model's
        dtype."""
        dtype = self.cfg.dtype
        rank = kind.latent_rank
        with named_span("attn_proj"), named_span("mla_absorb_q"):
            q = jnp.einsum("...nd,ncd->...nc", q_nope, lp["w_uk"],
                           preferred_element_type=jnp.float32).astype(dtype)
            q = jnp.concatenate(
                [q, q_rope, jnp.zeros(q.shape[:-1] + (
                    lanes - rank - kind.rotary_dim,), dtype)], axis=-1)
        with named_span("attention"):
            out = kernel(q)
        with named_span("attn_proj"), named_span("mla_expand_o"):
            ctx = jnp.einsum("...nc,ncd->...nd", out, lp["w_uv"],
                             preferred_element_type=jnp.float32)
            return ctx.reshape(ctx.shape[:-2] + (-1,)).astype(dtype)

    @staticmethod
    def _latent_scale(kind):
        return (kind.softmax_scale if kind.softmax_scale is not None
                else kind.k_dim ** -0.5)

    def _walk(self, params, x, positions, live, arenas, attend,
              attend_latent):
        """The layers in order on ``x [tokens, hidden]`` (``live [tokens]``:
        the rows that are tokens, not padding).  ``attend(kind,
        group, q, k, v, layer_arenas, sinks) -> (ctx [tokens, heads *
        v_dim], layer_arenas)`` appends the rows and runs the paged
        kernel; ``attend_latent(kind, group, lp, q_nope, q_rope, rows,
        index, layer_arenas)`` likewise for a latent kind (``index``: the
        indexer's rows of a sparse kind, else ``None``).  Returns ``(x,
        arenas, pairs [expert layers, held], chosen [expert layers, tokens,
        top_k], reached [expert layers])``."""
        cfg, spec = self.cfg, self.spec
        dtype = cfg.dtype
        # the residual stream stays in fp32 (each layer adds a small term to
        # it; in bf16 every add would round the whole stream); the GEMMs'
        # operands are the model's dtype
        with named_span("embed"):
            x = x.astype(jnp.float32)
        arenas = [list(group) for group in arenas]
        pairs, chosen, reached = [], [], []
        for layer, lp in enumerate(params.layers):
            kind = spec.kinds[spec.layer_kinds[layer]]
            gi, li = self.place[layer]
            n, g = kind.num_heads, kind.kv_heads
            with named_span("norm"):
                h1 = self._norm(x, lp["norm1"]).astype(dtype)
            if kind.latent:
                ctx, arenas[gi][li] = attend_latent(
                    kind, gi, lp, *self._latent_rows(
                        lp, h1, positions, kind,
                        self.cache.groups[gi].row_lanes), arenas[gi][li])
            else:
                with named_span("attn_proj"):
                    q = self._mm(h1, lp["wq"]).reshape(-1, n, kind.k_dim)
                    k = self._mm(h1, lp["wk"]).reshape(-1, g, kind.k_dim)
                    v = (self._mm(h1, lp["wv"])
                         * spec.value_scale).astype(dtype)
                with named_span("rope"):
                    q = self._rotate(q, positions, kind).astype(dtype)
                    k = self._rotate(k, positions, kind).astype(dtype)
                ctx, arenas[gi][li] = attend(
                    kind, gi, q, k.reshape(-1, g * kind.k_dim), v,
                    arenas[gi][li], lp["sinks"] if kind.sink else None)
            with named_span("attn_proj"):
                x = x + self._mm(ctx, lp["wo"])
            with named_span("norm"):
                h2 = self._norm(x, lp["norm2"]).astype(dtype)
            if spec.layer_experts[layer]:
                ex = spec.experts
                more = dict(ex.routing)
                if ex.route_scale != 1.0:
                    more["route_scale"] = ex.route_scale
                if ex.shared_experts:
                    more["shared"] = (lp["shared_gate_up"],
                                      lp["shared_down"])
                y, routed, experts, tokens = moe.held_experts_ffn(
                    h2, lp["router"], lp.get("router_bias"),
                    lp["experts_gate_up"], lp["experts_down"],
                    top_k=ex.top_k, held=ex.held, live=live, **more)
                pairs.append(routed)
                chosen.append(experts)
                reached.append(tokens)
                # the residual takes the experts' sum where it is made
                with named_span("moe_experts"):
                    x = x + y
            else:
                with named_span("dense_ffn"):
                    f = lp["ffn_down"].shape[0]
                    gate_up = self._mm(h2, lp["ffn_gate_up"])
                    mid = (jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:])
                    x = x + self._mm(mid.astype(dtype), lp["ffn_down"])
        held, top_k = ((spec.experts.held[1], spec.experts.top_k)
                       if spec.experts is not None else (0, 0))
        with named_span("moe_router"):
            # what each call tells the host of its routing, in one array
            pairs = (jnp.stack(pairs) if pairs
                     else jnp.zeros((0, held), jnp.int32))
            chosen = (jnp.stack(chosen) if chosen
                      else jnp.zeros((0, x.shape[0], top_k), jnp.int32))
            reached = (jnp.stack(reached) if reached
                       else jnp.zeros((0,), jnp.int32))
        return (x, tuple(tuple(group) for group in arenas), pairs, chosen,
                reached)

    def _logits(self, params, x):
        """fp32 logits ``[rows, vocab]`` of ``x [rows, hidden]``."""
        with named_span("norm"):
            x = self._norm(x, params.final_norm).astype(self.cfg.dtype)
        with named_span("lm_head"):
            return self._mm(x, params.head)

    def _kernel_kwargs(self, kind, sinks):
        return dict(kv_heads=kind.kv_heads, window=kind.window, sinks=sinks)

    # ---------------------------------------------------------------- entry

    def decode_step(self, arenas, params, tokens, positions, block_tables,
                    active, n_draft, temperature, top_k, top_p, seeds,
                    steps):
        """One continuously-batched decode step: :meth:`DecodeModel.
        decode_step`'s contract at ``spec_width`` 1 (``n_draft`` is taken
        and must be zero), with a tuple of block tables and two more
        results: ``pairs [expert layers, held experts]`` int32, the
        ``(token, expert)`` pairs this step routed to each held expert,
        ``chosen [expert layers, max_batch, top_k]`` int32, the experts
        each slot's router chose, and ``reached [expert layers]`` int32,
        the live slots whose router kept a group of experts held here.
        Returns ``(arenas, out_tokens [max_batch, 1], accepted [max_batch],
        logits [max_batch, 1, vocab], pairs, chosen, reached)``, and where
        a layer attends sparsely ``selected [sparse layers, max_batch,
        index_topk]`` after them: the positions each slot's query attended
        in each such layer, ascending, ``-1`` where no token."""
        del n_draft
        bs = self.cache.block_size
        B = tokens.shape[0]
        positions = positions.astype(jnp.int32)
        with named_span("attention"):
            lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
        with named_span("cache_write"):
            logical = positions // bs
            dest_offsets = positions % bs
        attend = (paged_attention_decode if self.fused_attention
                  else paged_attention_decode_unfused)
        attend_rows = (paged_decode_latent if self.fused_attention
                       else paged_decode_latent_unfused)

        def destination(gi):
            table = block_tables[gi]
            phys = jnp.take_along_axis(
                table, jnp.clip(logical, 0, table.shape[1] - 1)[:, None],
                axis=1)[:, 0]
            # inactive slots write out of range and the scatter drops them
            return table, jnp.where(active, phys,
                                    self.cache.groups[gi].n_blocks)

        picked = []               # each sparse layer's selections

        def latent_core(kind, gi, lp, q_nope, q_rope, rows, index,
                        layer_arenas):
            with named_span("cache_write"):
                table, dest = destination(gi)
                arena = layer_arenas[0].at[dest, dest_offsets].set(
                    rows.astype(layer_arenas[0].dtype), mode="drop")
                if index is not None:
                    keys = layer_arenas[1].at[dest, dest_offsets].set(
                        index[2].astype(layer_arenas[1].dtype), mode="drop")
            if index is None:
                ctx = self._latent_attention(
                    kind, lp, q_nope, q_rope, arena.shape[-1],
                    lambda q: attend_rows(q, arena, table, lengths,
                                          v_dim=kind.latent_rank,
                                          scale=self._latent_scale(kind)))
                return ctx, (arena,)

            def attend_sparse(q):
                out, selected = self._sparse(
                    kind, q[:, None], (index[0][:, None], index[1][:, None]),
                    arena, keys, table, lengths[:, None])
                picked.append(selected[:, 0])
                return out[:, 0]

            ctx = self._latent_attention(kind, lp, q_nope, q_rope,
                                         arena.shape[-1], attend_sparse)
            return ctx, (arena, keys)

        def attn_core(kind, gi, q, k, v, layer_arenas, sinks):
            k_arena, v_arena = layer_arenas
            with named_span("cache_write"):
                table, dest = destination(gi)
                k_arena = k_arena.at[dest, dest_offsets].set(
                    k.astype(k_arena.dtype), mode="drop")
                v_arena = v_arena.at[dest, dest_offsets].set(
                    v.astype(v_arena.dtype), mode="drop")
            with named_span("attention"):
                ctx = attend(q, k_arena, v_arena, table, lengths,
                             **self._kernel_kwargs(kind, sinks))
                return (ctx.reshape(B, -1).astype(q.dtype),
                        (k_arena, v_arena))

        with named_span("embed"):
            x = params.embedding[tokens[:, 0]]
        x, arenas, pairs, chosen, reached = self._walk(
            params, x, positions, active, arenas, attn_core, latent_core)
        logits = self._logits(params, x)                       # [B, vocab]
        with named_span("sample"):
            sampled = sample_tokens(logits, temperature, top_k, top_p,
                                    seeds, steps)
            out = jnp.where(active, sampled, 0).astype(jnp.int32)[:, None]
        outs = (arenas, out, jnp.zeros((B,), jnp.int32), logits[:, None],
                pairs, chosen, reached)
        if self.sparse:
            outs += (jnp.stack(picked),)
        return outs

    def prefill(self, arenas, params, tokens, position_ids, block_tables,
                lengths, limits, sample_index, temperature, top_k, top_p,
                seeds, steps):
        """Batched chunked prefill of one ``[max_batch, chunk]`` slice:
        :meth:`DecodeModel.prefill`'s contract with a tuple of block
        tables, the rows' destinations read from them in the graph
        (``limits == 0`` marks padding), and the logits of the sampled row
        only.  Returns ``(arenas, next_tokens [max_batch], logits
        [max_batch, 1, vocab], pairs, chosen [expert layers, max_batch *
        chunk, top_k], reached [expert layers])``."""
        bs = self.cache.block_size
        B, T = tokens.shape
        position_ids = position_ids.astype(jnp.int32)
        limits = limits.astype(jnp.int32)
        lengths = lengths.astype(jnp.int32)
        with named_span("cache_write"):
            real = limits > 0
            logical = position_ids // bs
            dest_offsets = position_ids % bs
        attend = (paged_prefill_attention if self.fused_attention
                  else paged_prefill_attention_unfused)
        attend_rows = (paged_prefill_latent if self.fused_attention
                       else paged_prefill_latent_unfused)

        def destination(gi):
            table = block_tables[gi]
            phys = jnp.take_along_axis(
                table, jnp.clip(logical, 0, table.shape[1] - 1), axis=1)
            return table, jnp.where(real, phys,
                                    self.cache.groups[gi].n_blocks)

        def latent_core(kind, gi, lp, q_nope, q_rope, rows, index,
                        layer_arenas):
            with named_span("cache_write"):
                table, dest = destination(gi)
                arena = layer_arenas[0].at[dest, dest_offsets].set(
                    rows.reshape(B, T, -1).astype(layer_arenas[0].dtype),
                    mode="drop")
                if index is not None:
                    keys = layer_arenas[1].at[dest, dest_offsets].set(
                        index[2].reshape(B, T, -1).astype(
                            layer_arenas[1].dtype), mode="drop")
            n = kind.num_heads
            # a few slots at a time: the absorbed queries of the whole call
            # would be ``B T n lanes`` elements (and a sparse kind's index
            # scores and their images ``B T max_seq`` float32 each)
            a_slot = T * n * arena.shape[-1] * arena.dtype.itemsize
            if index is not None:
                a_slot += 9 * T * table.shape[1] * self.cache.block_size
            walk = _slots_a_walk(B, a_slot)

            def some_slots(part):
                q_nope, q_rope, table, lengths, limits = part[:5]
                if index is not None:
                    selected = self._prefill_selection(
                        kind, part[5:], keys, table, limits)
                    return self._latent_attention(
                        kind, lp, q_nope, q_rope, arena.shape[-1],
                        lambda q: attend_rows(
                            q, arena, table, lengths, limits,
                            v_dim=kind.latent_rank,
                            scale=self._latent_scale(kind),
                            selected=selected))
                return self._latent_attention(
                    kind, lp, q_nope, q_rope, arena.shape[-1],
                    lambda q: attend_rows(q, arena, table, lengths, limits,
                                          v_dim=kind.latent_rank,
                                          scale=self._latent_scale(kind)))

            # the walk's own slices and their way back are the attention's
            with named_span("attention"):
                arrays = (q_nope.reshape(B, T, n, -1),
                          q_rope.reshape(B, T, n, -1), table, lengths, limits)
                if index is not None:
                    arrays += (index[0].reshape((B, T) + index[0].shape[1:]),
                               index[1].reshape(B, T, -1))
                parts = tuple(a.reshape((B // walk, walk) + a.shape[1:])
                              for a in arrays)
                ctx = (some_slots(tuple(a[0] for a in parts)) if walk == B
                       else lax.map(some_slots, parts))
                out = (arena,) if index is None else (arena, keys)
                return ctx.reshape(B * T, -1), out

        def attn_core(kind, gi, q, k, v, layer_arenas, sinks):
            k_arena, v_arena = layer_arenas
            with named_span("cache_write"):
                table, dest = destination(gi)
                k_arena = k_arena.at[dest, dest_offsets].set(
                    k.reshape(B, T, -1).astype(k_arena.dtype), mode="drop")
                v_arena = v_arena.at[dest, dest_offsets].set(
                    v.reshape(B, T, -1).astype(v_arena.dtype), mode="drop")
            with named_span("attention"):
                ctx = attend(q.reshape(B, T, kind.num_heads, kind.k_dim),
                             k_arena, v_arena, table, lengths, limits,
                             **self._kernel_kwargs(kind, sinks))
                return (ctx.reshape(B * T, -1).astype(q.dtype),
                        (k_arena, v_arena))

        with named_span("embed"):
            x = params.embedding[tokens.reshape(-1)]
        x, arenas, pairs, chosen, reached = self._walk(
            params, x, position_ids.reshape(-1), real.reshape(-1), arenas,
            attn_core, latent_core)
        with named_span("lm_head"):
            idx = sample_index.astype(jnp.int32)
            last = x.reshape(B, T, -1)[jnp.arange(B),
                                       jnp.clip(idx, 0, T - 1)]
        logits = self._logits(params, last)                    # [B, vocab]
        with named_span("sample"):
            sampled = sample_tokens(logits, temperature, top_k, top_p,
                                    seeds, steps)
            valid = (idx >= 0) & (idx < T)
            next_tokens = jnp.where(valid, sampled, 0).astype(jnp.int32)
        return arenas, next_tokens, logits[:, None], pairs, chosen, reached
