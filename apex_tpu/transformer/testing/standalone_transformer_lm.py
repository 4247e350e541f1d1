"""Standalone Megatron-style transformer language model.

Behavioral spec: ``apex/transformer/testing/standalone_transformer_lm.py`` —
``ParallelMLP:165``, ``CoreAttention:213``, ``ParallelAttention:358``,
``ParallelTransformerLayer:598``, ``ParallelTransformer:780``,
``Embedding:1239``, ``TransformerLanguageModel:1358``,
``parallel_lm_logits:1130`` — the reference's production-shaped GPT/BERT used
by every distributed test and the GPT scaling harness.

TPU-first notes
---------------
- Configuration is one dataclass (:class:`TransformerConfig`) instead of the
  977-line Megatron argparser (``testing/arguments.py``) — SURVEY.md §5
  config-system note.  Field names follow the reference's args.
- Activations use the Megatron ``[s, b, h]`` layout so Megatron-style
  sequence parallelism (first-dim sharding,
  ``tensor_parallel/mappings.py:63-139``) applies unchanged.
- Tensor parallelism: modules take the mesh axis name; run the model inside
  ``shard_map`` with that axis bound (or ``tensor_model_parallel_size=1``
  for plain jit).  XLA inserts/overlaps the collectives the reference
  hand-schedules.
- Pipeline parallelism: :class:`ParallelTransformerLayer` is the homogeneous
  stage unit; stack per-layer params with
  :func:`~apex_tpu.transformer.pipeline_parallel.stack_stage_params` and
  drive them with :func:`~apex_tpu.transformer.pipeline_parallel.pipeline_apply`
  (embedding/head live outside the pipelined region — see
  ``standalone_gpt.py``).
- Modern-architecture options beyond the reference's testing GPT
  (parity-plus, from its Megatron lineage): RoPE / NoPE
  (``position_embedding_type``, ``transformer/rope.py``), grouped-query
  attention (``num_query_groups`` — group-major fused QKV so tp chops
  land on whole groups), and SwiGLU (``swiglu`` — separate gate/up
  column linears, TP-exact).  All compose with tp/sp/cp and the flash
  path; defaults reproduce the reference exactly.
- Dropout uses the flax ``"dropout"`` rng; pass seeds derived with
  :func:`apex_tpu.transformer.tensor_parallel.random.model_parallel_rng_key`
  so tp ranks decorrelate exactly like the reference's
  ``model_parallel_cuda_manual_seed`` (``random.py:204``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.ops.softmax import AttnMaskType, FusedScaleMaskSoftmax
from apex_tpu.parallel.collectives import bound_axis_size
from apex_tpu.parallel.mesh import TENSOR_AXIS
from apex_tpu.transformer.enums import AttnType, LayerType
from apex_tpu.transformer.layers.layer_norm import FusedLayerNorm
from apex_tpu.transformer.rope import YarnScaling
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from apex_tpu.transformer.tensor_parallel import mappings
from apex_tpu.transformer.tensor_parallel.utils import divide

__all__ = [
    "TransformerConfig",
    "AttentionKind",
    "ExpertSpec",
    "HybridSpec",
    "HybridParams",
    "ParallelMLP",
    "CoreAttention",
    "ParallelAttention",
    "ParallelTransformerLayer",
    "ParallelTransformer",
    "Embedding",
    "TransformerLanguageModel",
    "parallel_lm_logits",
    "Pooler",
]


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """One kind of attention layer of a :class:`HybridSpec`: its head
    counts, the q/k width beside the v width, the rotated leading channels
    (``0``: the kind has no position signal at all) and their base, an
    optional sliding window (position ``i`` sees keys ``i - window < j <=
    i``: the token itself counts), whether each head has a learned sink
    logit (one more term of the softmax denominator that takes no value),
    whether each q and k head is RMS-normed over its channels before the
    rotation (``qk_norm``: one gain of ``k_dim`` each) and whether the
    attention output is multiplied by the sigmoid of a projection of the
    layer's input (``gate``).

    A **latent** kind (``latent_rank > 0``; multi-head latent attention,
    arXiv:2405.04434) projects its input down to ``q_rank`` channels for
    the queries and to ``latent_rank + rotary_dim`` for keys and values,
    RMS-norms both low-rank vectors, and expands per head: ``k_dim =
    nope_dim + rotary_dim`` query channels of which the *trailing*
    ``rotary_dim`` rotate (channel ``2 t`` paired with ``2 t + 1``), one
    rotary key shared by all heads (``kv_heads`` 1), ``nope_dim`` key and
    ``v_dim`` value channels a head expanded from the latent.  A token's
    cached row is the normed latent beside the rotated key
    (:attr:`cache_row`), whatever the head count.  ``rotary_scaling``
    stretches the rotary base (:class:`~apex_tpu.transformer.rope.
    YarnScaling`) and ``softmax_scale`` replaces ``k_dim ** -0.5``."""

    name: str
    num_heads: int
    kv_heads: int
    k_dim: int
    v_dim: int
    rotary_dim: int
    rotary_base: float = 10000.0
    window: Optional[int] = None
    sink: bool = False
    qk_norm: bool = False
    gate: bool = False
    latent_rank: int = 0
    q_rank: int = 0
    nope_dim: int = 0
    rotary_scaling: Optional[YarnScaling] = None
    softmax_scale: Optional[float] = None

    def __post_init__(self):
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"{self.name}: kv_heads ({self.kv_heads}) must divide "
                f"num_heads ({self.num_heads})")
        if self.rotary_dim % 2 or not 0 <= self.rotary_dim <= self.k_dim:
            raise ValueError(
                f"{self.name}: rotary_dim ({self.rotary_dim}) must be even "
                f"and at most k_dim ({self.k_dim})")
        if self.window is not None and self.window < 1:
            raise ValueError(f"{self.name}: window must be positive")
        if self.latent and (
                self.kv_heads != 1 or self.q_rank < 1 or self.window
                or self.nope_dim + self.rotary_dim != self.k_dim
                or self.sink or self.qk_norm or self.gate):
            raise ValueError(
                f"{self.name}: a latent kind has one shared key row "
                "(kv_heads 1), a q_rank, k_dim = nope_dim + rotary_dim, and "
                "no window, sink, qk_norm or gate")

    @property
    def latent(self) -> bool:
        return self.latent_rank > 0

    @property
    def cache_row(self) -> Tuple[int, int, int, bool]:
        """What a token and layer of this kind keep in the cache:
        ``(kv_heads, key width, value width, latent)``.  A latent row is
        one vector of ``latent_rank + rotary_dim`` channels whose leading
        ``latent_rank`` are also the values; any other kind keeps ``k_dim``
        beside ``v_dim`` per KV head."""
        if self.latent:
            return (1, self.latent_rank + self.rotary_dim, self.latent_rank,
                    True)
        return (self.kv_heads, self.k_dim, self.v_dim, False)


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """The expert feed-forward of a :class:`HybridSpec`: ``scoring``
    (``"sigmoid"`` or ``"softmax"``) scores over ``n_experts``, the
    ``top_k`` largest ``score + bias`` chosen (the bias selects and does
    not weigh) among the ``topk_groups`` of ``n_groups`` groups of
    consecutive experts whose best ``score + bias`` is largest (one group:
    no limit), their scores over their sum plus ``route_eps`` (as they are
    where ``normalize`` is false), times ``route_scale``, each expert a
    SwiGLU of width ``ffn_size``.  ``held = (first, count)`` are the experts
    whose weights this process holds: the layer routes over all
    ``n_experts`` and computes the held experts' part of the result
    (:func:`apex_tpu.transformer.moe.held_experts_ffn`).
    ``shared_experts`` of them more are met by every token, as one dense
    SwiGLU of width ``shared_experts * ffn_size`` that every chip computes
    for its own tokens."""

    n_experts: int
    top_k: int
    ffn_size: int
    held: Tuple[int, int]
    shared_experts: int = 0
    route_scale: float = 1.0
    route_eps: float = 0.0
    scoring: str = "sigmoid"
    n_groups: int = 1
    topk_groups: int = 1
    normalize: bool = True

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"held experts {self.held} lie outside 0..{self.n_experts}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(
                f"top_k ({self.top_k}) must lie in 1..{self.n_experts}")
        if self.shared_experts < 0:
            raise ValueError("shared_experts must not be negative")
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {self.scoring!r} is not known")
        if (self.n_experts % self.n_groups
                or not 1 <= self.topk_groups <= self.n_groups
                or self.top_k > self.topk_groups
                * (self.n_experts // self.n_groups)):
            raise ValueError(
                f"{self.topk_groups} of {self.n_groups} groups do not hold "
                f"{self.top_k} of {self.n_experts} experts")

    @property
    def routing(self) -> dict:
        """What :func:`~apex_tpu.transformer.moe.route_topk` takes beyond
        its defaults, by keyword (empty for the sigmoid family)."""
        out = {}
        if self.scoring != "sigmoid":
            out["scoring"] = self.scoring
        if self.n_groups != 1:
            out["groups"] = (self.n_groups, self.topk_groups)
        if not self.normalize:
            out["normalize"] = False
        return out


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """A per-layer description: which :class:`AttentionKind` each layer
    runs and whether its feed-forward is the dense SwiGLU
    (``ffn_hidden_size`` wide) or the expert layer.  Layers of this family
    are RMSNorm residual blocks with no biases, rotary positions on the
    leading ``rotary_dim`` channels of q and k (half rotation), values
    scaled by ``value_scale``, a final RMSNorm and an untied output head.
    They are pre-norm blocks, ``h + F(norm(h))``; with ``sandwich_norm``
    each sublayer's output is normed once more before it is added, ``h +
    norm_post(F(norm(h)))``.  The token embedding is multiplied by
    ``embedding_multiplier``.

    One description drives the trainer
    (``testing.hybrid_train``, reached through ``build_gpt_3d``), the
    server (``serving.model.HybridDecodeModel``, which refuses by name
    what it does not implement yet) and a benchmark's plain reference.  A
    model whose layers are all alike needs none of this: it leaves
    ``TransformerConfig.hybrid`` at ``None`` and is the program it always
    was."""

    kinds: Tuple[AttentionKind, ...]
    layer_kinds: Tuple[int, ...]          # per layer: index into ``kinds``
    layer_experts: Tuple[bool, ...]       # per layer: expert feed-forward
    experts: Optional[ExpertSpec] = None
    value_scale: float = 1.0
    sandwich_norm: bool = False
    embedding_multiplier: float = 1.0

    def __post_init__(self):
        if len(self.layer_kinds) != len(self.layer_experts):
            raise ValueError("layer_kinds and layer_experts differ in length")
        if any(not 0 <= k < len(self.kinds) for k in self.layer_kinds):
            raise ValueError("layer_kinds names a kind that is not there")
        if any(self.layer_experts) and self.experts is None:
            raise ValueError("expert layers need an ExpertSpec")

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        """The layers that run attention kind ``kind``, in order."""
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)


class HybridParams(NamedTuple):
    """Parameters of a model described by a :class:`HybridSpec`, trained
    (float32 leaves) or served (the model's dtype).

    ``embedding [vocab, hidden]``; ``layers``: one dict per layer, in
    order (``norm1``, ``wq [hidden, heads * k_dim]``, ``wk [hidden,
    kv_heads * k_dim]``, ``wv [hidden, kv_heads * v_dim]``, ``wo [heads *
    v_dim, hidden]``, ``sinks [heads]`` where the layer's kind has them,
    ``q_norm`` and ``k_norm [k_dim]`` where it norms q and k, ``wg
    [hidden, heads * v_dim]`` where it has a gate; a latent kind has, in
    place of ``wq``, ``wk`` and ``wv``, ``wq_a [hidden, q_rank]``,
    ``q_a_norm [q_rank]``, ``wq_b [q_rank, heads * k_dim]``, ``wkv_a
    [hidden, latent_rank + rotary_dim]``, ``kv_a_norm [latent_rank]``,
    ``w_uk [heads, latent_rank, nope_dim]`` and ``w_uv [heads, latent_rank,
    v_dim]``; ``norm2``,
    ``post_attn_norm`` and ``post_ffn_norm [hidden]`` under
    ``sandwich_norm``, then ``ffn_gate_up [hidden, 2 f]`` and ``ffn_down
    [f, hidden]`` for a dense layer or ``router [hidden, E]``,
    ``router_bias [E]`` (may be absent: no bias), ``experts_gate_up [held,
    hidden, 2 f]`` and
    ``experts_down [held, f, hidden]`` for an expert layer, with
    ``shared_gate_up [hidden, 2 f']`` and ``shared_down [f', hidden]``
    where there are shared experts; gate columns come first);
    ``final_norm [hidden]``; ``head [hidden, vocab]`` (untied)."""

    embedding: Any
    layers: Tuple[dict, ...]
    final_norm: Any
    head: Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """The argparser surface the standalone LM consumes
    (``testing/arguments.py`` defaults), as a dataclass."""

    hidden_size: int = 128
    num_layers: int = 2
    num_attention_heads: int = 8
    ffn_hidden_size: Optional[int] = None  # default 4*hidden
    kv_channels: Optional[int] = None      # default hidden/heads
    padded_vocab_size: int = 1024
    max_position_embeddings: int = 512

    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    init_method_std: float = 0.02
    layernorm_epsilon: float = 1e-5

    apply_query_key_layer_scaling: bool = True
    attention_softmax_in_fp32: bool = False
    apply_residual_connection_post_layernorm: bool = False
    bias_gelu_fusion: bool = True
    masked_softmax_fusion: bool = True
    # Pallas flash attention for the causal core (no score matrix in HBM);
    # falls back to the fused-softmax path for padding masks / dropout.
    use_flash_attention: bool = False

    sequence_parallel: bool = False
    tensor_axis: Optional[str] = TENSOR_AXIS  # None = no tensor parallelism
    # Ring-decomposed collective matmul on every Column/Row parallel linear
    # (tensor_parallel/overlap.py): the SP all-gather/reduce-scatter is
    # pipelined under partial GEMMs, one collective-permute hop at a time,
    # forward and backward.  Only changes the schedule (and only where
    # sequence_parallel puts a collective on the layer); values and grads
    # match the monolithic path to fp32 tolerance.
    overlap_comm: bool = False
    # Context parallelism (ring attention over a cp mesh axis): activations
    # carry the LOCAL sequence shard [s/cp, b, h]; the causal core runs
    # :func:`apex_tpu.transformer.context_parallel.ring_attention`.  Run the
    # model inside shard_map with this axis bound (gpt_cp_train.py is the
    # worked harness).  Mutually exclusive with sequence_parallel
    # (validated in __post_init__); causal attention only (enforced in
    # CoreAttention).
    context_axis: Optional[str] = None
    # "ring" (K/V chunks rotate via ppermute; any head count) or "ulysses"
    # (all_to_all head<->sequence swap; needs heads % cp == 0, one a2a pair
    # instead of cp neighbor hops).
    context_impl: str = "ring"

    def __post_init__(self):
        if self.context_axis is not None and self.sequence_parallel:
            raise ValueError(
                "context_axis and sequence_parallel are mutually exclusive:"
                " both reinterpret the sequence dimension as sharded (over"
                " cp and tp respectively) and composing them would compute"
                " attention over a misread shard layout")
        if self.context_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"context_impl must be 'ring' or 'ulysses', got "
                f"{self.context_impl!r}")
        if self.position_embedding_type not in ("learned", "rope", "none"):
            raise ValueError(
                f"position_embedding_type must be 'learned', 'rope' or "
                f"'none', got {self.position_embedding_type!r}")
        if not 0.0 < self.rotary_percent <= 1.0:
            raise ValueError(
                f"rotary_percent must be in (0, 1], got "
                f"{self.rotary_percent} (use position_embedding_type="
                f"'none' for no position signal)")
        if (self.hybrid is not None
                and len(self.hybrid.layer_kinds) != self.num_layers):
            raise ValueError(
                f"hybrid describes {len(self.hybrid.layer_kinds)} layers, "
                f"num_layers is {self.num_layers}")
        if (self.num_query_groups is not None
                and (self.num_query_groups <= 0
                     or self.num_attention_heads % self.num_query_groups)):
            raise ValueError(
                f"num_query_groups ({self.num_query_groups}) must be "
                f"positive and divide num_attention_heads "
                f"({self.num_attention_heads})")

    # Mixture-of-experts (parity-plus: the reference stubs SwitchMLP out,
    # standalone_transformer_lm.py:675; see apex_tpu/transformer/moe.py).
    num_experts: Optional[int] = None
    expert_capacity_factor: float = 1.25
    expert_axis: Optional[str] = None

    # --- modern-architecture options (parity-plus: the reference's testing
    # GPT is learned-positions/MHA/GeLU only; its Megatron lineage grew
    # RoPE/GQA/SwiGLU and this stack supports them across tp/sp/cp) ---
    # "learned" (reference behavior), "rope" (rotary on q/k, no position
    # table — see transformer/rope.py), or "none" (NoPE).
    position_embedding_type: str = "learned"
    rotary_base: float = 10000.0
    # fraction of head_dim rotated (Megatron --rotary-percent)
    rotary_percent: float = 1.0
    # Grouped-query attention: number of K/V head groups (None = MHA,
    # 1 = MQA).  Must divide num_attention_heads; under tensor
    # parallelism the tp world size must divide it (groups are
    # column-sharded alongside their query heads).
    num_query_groups: Optional[int] = None
    # LLaMA-style gated MLP: silu(gate(x)) * up(x) with separate gate/up
    # column linears (TP-exact under any tp size; ffn_hidden_size is NOT
    # auto-scaled by 2/3 — set it explicitly for iso-params).
    swiglu: bool = False

    # Layers of more than one kind (window beside full attention, an
    # expert feed-forward beside a dense one): a :class:`HybridSpec`, whose
    # layers are RMSNorm blocks (the uniform blocks are LayerNorm only).
    # Served by ``serving.model.HybridDecodeModel``, trained by
    # ``testing.hybrid_train`` through ``build_gpt_3d``; ``None`` = every
    # layer alike, as the fields above describe it.
    hybrid: Optional[HybridSpec] = None

    dtype: Any = jnp.float32        # compute dtype (bf16 under the O2 policy)
    param_dtype: Any = jnp.float32

    # FP8 transformer-layer GEMMs (qkv / attention out / fc1 / fc2) via
    # :func:`apex_tpu.amp.fp8.fp8_matmul_t`: e4m3 operands with delayed
    # scaling, e5m2 just-in-time cotangents, amax pmax-shared over
    # ``tensor_axis`` (the reference's TE amax groups,
    # ``apex/transformer/parallel_state.py:280-291``).  The delayed scales
    # live in the mutable ``"fp8_meta"`` collection — train steps apply with
    # ``mutable=["fp8_meta"]`` and carry the collection forward (see
    # ``tests/test_fp8.py::test_fp8_gpt_trains``).  Embedding/LM head stay
    # in the compute dtype (the TE recipe).
    fp8: bool = False

    @property
    def ffn_size(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.kv_channels or divide(self.hidden_size,
                                          self.num_attention_heads)

    @property
    def query_groups(self) -> int:
        """K/V head groups (== num_attention_heads for MHA)."""
        return self.num_query_groups or self.num_attention_heads

    @property
    def rotary_dim(self) -> int:
        """Rotated leading channels of each head (even, >= 2)."""
        return max(2, int(self.head_dim * self.rotary_percent) // 2 * 2)

    def init_method(self):
        """``init_method_normal`` (reference ``:96-103``)."""
        return nn.initializers.normal(stddev=self.init_method_std)

    def scaled_init_method(self):
        """``scaled_init_method_normal`` — std/sqrt(2*num_layers) for
        output-facing weights (reference ``:105-114``)."""
        return nn.initializers.normal(
            stddev=self.init_method_std / math.sqrt(2.0 * self.num_layers)
        )


class ParallelMLP(nn.Module):
    """h → 4h (column, gelu) → h (row).  Reference ``ParallelMLP:165-212``:
    the first GEMM keeps its output sharded, bias+gelu fuse
    (``bias_gelu_fusion``), the second GEMM all-reduces (or
    reduce-scatters under SP)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h, bias = ColumnParallelLinear(
            cfg.hidden_size, cfg.ffn_size,
            sequence_parallel=cfg.sequence_parallel,
            skip_bias_add=True,
            axis=cfg.tensor_axis,
            kernel_init=cfg.init_method(),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, fp8=cfg.fp8,
            overlap_comm=cfg.overlap_comm,
            name="dense_h_to_4h",
        )(x)
        if cfg.swiglu:
            # LLaMA-style gated MLP: a SEPARATE gate column linear (w1/w3
            # split) rather than one fused 2*ffn projection — the fused
            # form's gate/up split lands differently on each tp chop,
            # while two linears are TP-exact under any tp size.  XLA
            # fuses silu+multiply into one elementwise region between
            # the GEMMs.
            gate, gate_bias = ColumnParallelLinear(
                cfg.hidden_size, cfg.ffn_size,
                sequence_parallel=cfg.sequence_parallel,
                skip_bias_add=True,
                axis=cfg.tensor_axis,
                kernel_init=cfg.init_method(),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, fp8=cfg.fp8,
                overlap_comm=cfg.overlap_comm,
                name="dense_h_to_4h_gate",
            )(x)
            h = jax.nn.silu(gate + gate_bias) * (h + bias)
        else:
            # bias_gelu fusion (reference fused_bias_gelu.py): one fused
            # elementwise region under XLA either way.
            h = jax.nn.gelu(h + bias, approximate=cfg.bias_gelu_fusion)
        out, out_bias = RowParallelLinear(
            cfg.ffn_size, cfg.hidden_size,
            input_is_parallel=True,
            sequence_parallel=cfg.sequence_parallel,
            skip_bias_add=True,
            axis=cfg.tensor_axis,
            kernel_init=cfg.scaled_init_method(),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, fp8=cfg.fp8,
            overlap_comm=cfg.overlap_comm,
            name="dense_4h_to_h",
        )(h)
        return out, out_bias


class CoreAttention(nn.Module):
    """Scaled-dot-product attention core, reference ``CoreAttention:213-357``:
    BMM1 → FusedScaleMaskSoftmax → attention dropout → BMM2, with
    query-key layer scaling (scores divided by an extra ``layer_number``
    factor, compensated inside the softmax scale — the fp16 overflow guard)."""

    config: TransformerConfig
    layer_number: int = 1
    attn_mask_type: AttnMaskType = AttnMaskType.padding

    @nn.compact
    def __call__(self, q, k, v, mask, deterministic: bool = True,
                 segment_ids=None):
        cfg = self.config
        # q/k/v: [s, b, n_local, d]
        sq, b, n, d = q.shape
        sk = k.shape[0]

        if (cfg.context_axis is not None
                and self.attn_mask_type != AttnMaskType.causal):
            # Falling through to the fused-softmax path would silently
            # attend within the local [s/cp] shard only.
            raise NotImplementedError(
                "context_axis supports causal self-attention only; "
                "non-causal attention over a cp-sharded sequence needs "
                "ulysses_attention (context_parallel.py) wired explicitly")
        if (cfg.context_axis is not None
                and self.attn_mask_type == AttnMaskType.causal):
            # Context parallelism: q/k/v hold this rank's sequence shard.
            from apex_tpu.transformer import context_parallel as cp_lib

            kw = {}
            if cfg.attention_dropout > 0.0 and not deterministic:
                if cfg.context_impl == "ring":
                    # in-kernel dropout is not plumbed through the ring
                    # VJP (kernels re-driven per visiting chunk); reject
                    # rather than silently skip it
                    raise NotImplementedError(
                        "attention_dropout under ring context parallelism "
                        "is not supported; use context_impl='ulysses' or "
                        "set attention_dropout=0.0")
                kw = dict(
                    dropout_rate=cfg.attention_dropout,
                    dropout_seed=jax.random.randint(
                        self.make_rng("dropout"), (), 0,
                        jnp.iinfo(jnp.int32).max),
                )
            attn = (cp_lib.ring_attention if cfg.context_impl == "ring"
                    else cp_lib.ulysses_attention)
            ctx = attn(
                q.transpose(1, 2, 0, 3), k.transpose(1, 2, 0, 3),
                v.transpose(1, 2, 0, 3), axis=cfg.context_axis, causal=True,
                **kw,
            )  # [b, n, sq_local, d]
            return ctx.transpose(2, 0, 1, 3).reshape(sq, b, n * d)

        # Flash handles the causal mask natively and *padding* masks via
        # segment ids ([b, s] ints: real tokens share an id, padding gets a
        # different one — the both-sides-real semantics of
        # ``bert_extended_attention_mask``); an arbitrary [b,1,sq,sk] mask
        # has no flash form and falls through to the fused-softmax path.
        use_flash = cfg.use_flash_attention and (
            self.attn_mask_type == AttnMaskType.causal
            or (self.attn_mask_type == AttnMaskType.padding
                and segment_ids is not None))
        if use_flash:
            from apex_tpu.ops.flash_attention import flash_attention
            if cfg.attention_dropout > 0.0 and not deterministic:
                # In-kernel counter-based dropout: derive a per-call scalar
                # seed from the flax "dropout" rng stream (the analog of
                # the reference's CUDA philox offsets).
                seed = jax.random.randint(
                    self.make_rng("dropout"), (), 0, jnp.iinfo(jnp.int32).max
                )
                drop = dict(dropout_rate=cfg.attention_dropout,
                            dropout_seed=seed)
            else:
                drop = {}
            if segment_ids is not None:
                drop.update(segment_ids_q=segment_ids,
                            segment_ids_kv=segment_ids)
            ctx = flash_attention(
                q.transpose(1, 2, 0, 3), k.transpose(1, 2, 0, 3),
                v.transpose(1, 2, 0, 3),
                causal=self.attn_mask_type == AttnMaskType.causal, **drop,
            )  # [b, n, sq, d]
            return ctx.transpose(2, 0, 1, 3).reshape(sq, b, n * d)

        norm_factor = math.sqrt(d)
        coeff = None
        if cfg.apply_query_key_layer_scaling:
            coeff = max(1, self.layer_number)
            norm_factor *= coeff

        # BMM1: [b*n, sq, sk] on the MXU, accumulating fp32.
        qt = q.transpose(1, 2, 0, 3).reshape(b * n, sq, d)
        kt = k.transpose(1, 2, 0, 3).reshape(b * n, sk, d)
        scores = jnp.matmul(
            qt, kt.transpose(0, 2, 1),
            preferred_element_type=jnp.float32,
        ) / norm_factor
        scores = scores.reshape(b, n, sq, sk).astype(
            jnp.float32 if cfg.attention_softmax_in_fp32 else cfg.dtype
        )

        softmax = FusedScaleMaskSoftmax(
            input_in_fp16=cfg.dtype == jnp.float16,
            input_in_bf16=cfg.dtype == jnp.bfloat16,
            attn_mask_type=self.attn_mask_type,
            scaled_masked_softmax_fusion=cfg.masked_softmax_fusion,
            mask_func=None,
            softmax_in_fp32=True,
            scale=coeff,
        )
        probs = softmax(scores, mask)
        probs = nn.Dropout(rate=cfg.attention_dropout)(
            probs, deterministic=deterministic
        )
        probs = probs.astype(cfg.dtype)

        # BMM2 → context [s, b, n_local*d]
        ctx = jax.lax.batch_matmul(
            probs.reshape(b * n, sq, sk),
            v.transpose(1, 2, 0, 3).reshape(b * n, sk, d),
        )
        ctx = ctx.reshape(b, n, sq, d).transpose(2, 0, 1, 3)
        return ctx.reshape(sq, b, n * d)


class ParallelAttention(nn.Module):
    """Self/cross attention with TP-sharded heads.

    Reference ``ParallelAttention:358-597``: fused QKV column linear
    (3*h out-sharded), core attention over the local heads, row-linear output
    projection with the residual-facing scaled init."""

    config: TransformerConfig
    layer_number: int = 1
    attention_type: AttnType = AttnType.self_attn
    attn_mask_type: AttnMaskType = AttnMaskType.padding

    def _maybe_rotary(self, q, k):
        """Rotate q/k (RoPE) when configured; no-op otherwise.  Runs
        BEFORE the GQA broadcast (rotating ``g_local`` K heads, not
        ``n_local`` copies) and before any cp exchange — under context
        parallelism the positions are this rank's *global* token indices
        (shard offset + local arange), so rotated keys travel the
        ring/all-to-all already position-stamped."""
        cfg = self.config
        if cfg.position_embedding_type != "rope":
            return q, k
        from apex_tpu.transformer.rope import apply_rotary, rotary_cos_sin

        s_local = q.shape[0]
        positions = jnp.arange(s_local)
        if cfg.context_axis is not None:
            positions = positions + (
                jax.lax.axis_index(cfg.context_axis) * s_local)
        cos, sin = rotary_cos_sin(positions, cfg.rotary_dim,
                                  cfg.rotary_base, q.dtype)
        return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)

    @nn.compact
    def __call__(self, x, mask, encoder_output=None, deterministic=True,
                 segment_ids=None):
        cfg = self.config
        world = bound_axis_size(cfg.tensor_axis)
        n_local = divide(cfg.num_attention_heads, world)
        d = cfg.head_dim
        proj = cfg.num_attention_heads * d

        if self.attention_type == AttnType.self_attn:
            # Fused QKV in GROUP-MAJOR layout: for each of the
            # ``query_groups`` K/V groups, its ``heads_per_group`` query
            # heads then its one K and one V head — so the column-parallel
            # chop hands every tp rank whole groups and the layout is
            # identical for any tp size dividing ``query_groups``.  MHA
            # (groups == heads) degenerates to the per-head [q|k|v]
            # triples this module always used.
            g = cfg.query_groups
            hpg = divide(cfg.num_attention_heads, g)
            g_local = divide(g, world)
            qkv = ColumnParallelLinear(
                cfg.hidden_size, (cfg.num_attention_heads + 2 * g) * d,
                sequence_parallel=cfg.sequence_parallel,
                axis=cfg.tensor_axis,
                kernel_init=cfg.init_method(),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, fp8=cfg.fp8,
                overlap_comm=cfg.overlap_comm,
                name="query_key_value",
            )(x)
            s, b = qkv.shape[0], qkv.shape[1]
            qkv = qkv.reshape(s, b, g_local, (hpg + 2) * d)
            q = qkv[..., :hpg * d].reshape(s, b, n_local, d)
            k = qkv[..., hpg * d:(hpg + 1) * d]  # [s, b, g_local, d]
            v = qkv[..., (hpg + 1) * d:]
            q, k = self._maybe_rotary(q, k)
            if hpg > 1 and cfg.context_axis is None:
                # broadcast each K/V group across its query heads for the
                # single-rank flash/softmax cores (XLA fuses the repeat
                # into the operand read).  Under context parallelism the
                # grouped K/V passes through: ring/ulysses transfer the
                # compact g-head K/V over the interconnect and broadcast
                # locally per chunk (context_parallel._expand_kv) — the
                # GQA bandwidth saving is exactly the long-context win.
                k = jnp.repeat(k, hpg, axis=2)
                v = jnp.repeat(v, hpg, axis=2)
        else:
            q = ColumnParallelLinear(
                cfg.hidden_size, proj,
                sequence_parallel=cfg.sequence_parallel,
                axis=cfg.tensor_axis, kernel_init=cfg.init_method(),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, fp8=cfg.fp8,
                overlap_comm=cfg.overlap_comm,
                name="query",
            )(x)
            kv = ColumnParallelLinear(
                cfg.hidden_size, 2 * proj,
                sequence_parallel=False, axis=cfg.tensor_axis,
                kernel_init=cfg.init_method(),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, fp8=cfg.fp8,
                overlap_comm=cfg.overlap_comm,
                name="key_value",
            )(encoder_output)
            s, b = q.shape[0], q.shape[1]
            q = q.reshape(s, b, n_local, d)
            kv = kv.reshape(kv.shape[0], b, n_local, 2 * d)
            k, v = jnp.split(kv, 2, axis=-1)

        ctx = CoreAttention(
            cfg, layer_number=self.layer_number,
            attn_mask_type=self.attn_mask_type, name="core_attention",
        )(q, k, v, mask, deterministic=deterministic,
          segment_ids=segment_ids)

        out, bias = RowParallelLinear(
            proj, cfg.hidden_size,
            input_is_parallel=True,
            sequence_parallel=cfg.sequence_parallel,
            skip_bias_add=True,
            axis=cfg.tensor_axis,
            kernel_init=cfg.scaled_init_method(),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, fp8=cfg.fp8,
            overlap_comm=cfg.overlap_comm,
            name="dense",
        )(ctx)
        return out, bias


class ParallelTransformerLayer(nn.Module):
    """Pre-LN transformer block, reference ``ParallelTransformerLayer:598-779``:
    LN → attention → bias-dropout-residual → LN → MLP →
    bias-dropout-residual, with optional post-LN residual source
    (``apply_residual_connection_post_layernorm``)."""

    config: TransformerConfig
    layer_number: int = 1
    layer_type: LayerType = LayerType.encoder
    self_attn_mask_type: AttnMaskType = AttnMaskType.padding

    @nn.compact
    def __call__(self, x, mask, encoder_output=None, enc_dec_mask=None,
                 deterministic: bool = True, segment_ids=None):
        cfg = self.config
        ln1 = FusedLayerNorm(cfg.hidden_size, eps=cfg.layernorm_epsilon,
                             name="input_layernorm")(x)
        attn_out, attn_bias = ParallelAttention(
            cfg, layer_number=self.layer_number,
            attn_mask_type=self.self_attn_mask_type, name="self_attention",
        )(ln1, mask, deterministic=deterministic, segment_ids=segment_ids)
        residual = ln1 if cfg.apply_residual_connection_post_layernorm else x
        h = residual + nn.Dropout(rate=cfg.hidden_dropout)(
            attn_out + attn_bias, deterministic=deterministic
        )

        if self.layer_type == LayerType.decoder:
            ln_cross = FusedLayerNorm(
                cfg.hidden_size, eps=cfg.layernorm_epsilon,
                name="post_inter_attention_layernorm",
            )(h)
            cross_out, cross_bias = ParallelAttention(
                cfg, layer_number=self.layer_number,
                attention_type=AttnType.cross_attn,
                attn_mask_type=AttnMaskType.padding,
                name="inter_attention",
            )(ln_cross, enc_dec_mask, encoder_output=encoder_output,
              deterministic=deterministic)
            residual = (ln_cross
                        if cfg.apply_residual_connection_post_layernorm else h)
            h = residual + nn.Dropout(rate=cfg.hidden_dropout)(
                cross_out + cross_bias, deterministic=deterministic
            )

        ln2 = FusedLayerNorm(cfg.hidden_size, eps=cfg.layernorm_epsilon,
                             name="post_attention_layernorm")(h)
        if cfg.num_experts is not None:
            from apex_tpu.transformer.moe import SwitchMLP

            mlp_out, _aux = SwitchMLP(
                hidden_size=cfg.hidden_size, ffn_size=cfg.ffn_size,
                num_experts=cfg.num_experts,
                capacity_factor=cfg.expert_capacity_factor,
                expert_axis=cfg.expert_axis,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="mlp",
            )(ln2)
            # (1,)-shaped, NOT rank-0: this zero rides the gradient path
            # (mlp_out + mlp_bias), and under jax 0.4.x's old shard_map a
            # rank-0 value crossing the shard_map boundary in the
            # transposed (backward) program has no dimension to carry its
            # device-varying names — `_check_names` raises `_SpecError`
            # when the 3D trainer stages under `value_and_grad`.  The
            # singleton axis broadcasts identically and checks cleanly on
            # every jax version we shim.
            mlp_bias = jnp.zeros((1,), cfg.dtype)
        else:
            mlp_out, mlp_bias = ParallelMLP(cfg, name="mlp")(ln2)
        residual = ln2 if cfg.apply_residual_connection_post_layernorm else h
        return residual + nn.Dropout(rate=cfg.hidden_dropout)(
            mlp_out + mlp_bias, deterministic=deterministic
        )


class ParallelTransformer(nn.Module):
    """Layer stack + final LN, reference ``ParallelTransformer:780-1129``.

    ``post_process`` controls the final LayerNorm exactly like the
    reference's pipeline-stage flags; the per-layer loop is a Python loop
    (layers are distinct flax submodules with their own params — the
    pipelined path instead stacks layer params and uses ``pipeline_apply``).
    """

    config: TransformerConfig
    self_attn_mask_type: AttnMaskType = AttnMaskType.causal
    pre_process: bool = True
    post_process: bool = True

    @nn.compact
    def __call__(self, x, mask, deterministic: bool = True,
                 segment_ids=None):
        cfg = self.config
        for i in range(cfg.num_layers):
            x = ParallelTransformerLayer(
                cfg, layer_number=i + 1,
                self_attn_mask_type=self.self_attn_mask_type,
                name=f"layers_{i}",
            )(x, mask, deterministic=deterministic,
              segment_ids=segment_ids)
        if self.post_process:
            x = FusedLayerNorm(cfg.hidden_size, eps=cfg.layernorm_epsilon,
                               name="final_layernorm")(x)
        return x


class Embedding(nn.Module):
    """Word (vocab-parallel) + learned position embeddings + dropout,
    reference ``Embedding:1239-1357``.  Output is ``[s, b, h]``; under SP the
    caller scatters the sequence dim
    (``scatter_to_sequence_parallel_region``)."""

    config: TransformerConfig
    add_position_embedding: bool = True

    # setup-style so ``word_embeddings`` is shareable for the tied LM head.
    def setup(self):
        cfg = self.config
        # rope/none position types carry no learned position table — the
        # position signal lives in the attention rotation (or nowhere)
        self._learned_positions = (self.add_position_embedding
                                   and cfg.position_embedding_type
                                   == "learned")
        self.word_embeddings = VocabParallelEmbedding(
            cfg.padded_vocab_size, cfg.hidden_size,
            axis=cfg.tensor_axis,
            embedding_init=cfg.init_method(),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        )
        if self._learned_positions:
            self.position_embeddings = nn.Embed(
                cfg.max_position_embeddings, cfg.hidden_size,
                embedding_init=cfg.init_method(),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            )
        self.dropout = nn.Dropout(rate=cfg.hidden_dropout)

    def __call__(self, token_ids, position_ids=None, deterministic=True):
        cfg = self.config
        if position_ids is not None and not self._learned_positions:
            # RoPE derives positions inside the attention (arange +
            # cp-shard offset) and has no hook for caller ids yet;
            # dropping them silently would mis-rotate packed sequences.
            raise NotImplementedError(
                "custom position_ids are only honored with "
                "position_embedding_type='learned'; the rope path "
                "derives positions internally (packed-sequence resets "
                "are not yet supported under rope)")
        words = self.word_embeddings(token_ids)  # [b, s, h]
        if self._learned_positions:
            if position_ids is None:
                position_ids = jnp.arange(token_ids.shape[1])[None, :]
            words = words + self.position_embeddings(position_ids)
        x = words.transpose(1, 0, 2)  # [s, b, h] Megatron layout
        if cfg.sequence_parallel and bound_axis_size(cfg.tensor_axis) > 1:
            x = mappings.scatter_to_sequence_parallel_region(
                x, cfg.tensor_axis
            )
        return self.dropout(x, deterministic=deterministic)


class Pooler(nn.Module):
    """Tanh pooler over a sequence index, reference ``Pooler:1190-1238``."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, hidden, sequence_index: int = 0):
        cfg = self.config
        pooled = hidden[sequence_index]  # [b, h]
        return jnp.tanh(
            nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     kernel_init=cfg.init_method(), name="dense")(pooled)
        )


def parallel_lm_logits(hidden, word_embeddings, config: TransformerConfig,
                       bias=None):
    """LM head sharing the (vocab-sharded) embedding matrix.

    Reference ``parallel_lm_logits:1130-1189``: under SP first all-gather the
    sequence shards, then the column-parallel GEMM against the embedding
    table; output stays vocab-sharded for
    :func:`~apex_tpu.transformer.tensor_parallel.vocab_parallel_cross_entropy`.
    Input ``[s, b, h]`` → logits ``[s, b, vocab_local]``.
    """
    world = bound_axis_size(config.tensor_axis)
    if config.sequence_parallel and world > 1:
        hidden = mappings.gather_from_sequence_parallel_region(
            hidden, config.tensor_axis, True
        )
    elif world > 1:
        hidden = mappings.copy_to_tensor_model_parallel_region(
            hidden, config.tensor_axis
        )
    if hasattr(word_embeddings, "attend"):
        # Bound VocabParallelEmbedding module: tied-weight GEMM.
        logits = word_embeddings.attend(hidden)
    else:
        logits = jnp.einsum("sbh,vh->sbv", hidden,
                            jnp.asarray(word_embeddings, hidden.dtype))
    if bias is not None:
        logits = logits + bias
    return logits


class TransformerLanguageModel(nn.Module):
    """Embedding + transformer (+ pooler), reference
    ``TransformerLanguageModel:1358-1529``."""

    config: TransformerConfig
    self_attn_mask_type: AttnMaskType = AttnMaskType.causal
    add_pooler: bool = False

    def setup(self):
        cfg = self.config
        self.embedding = Embedding(cfg)
        self.encoder = ParallelTransformer(
            cfg, self_attn_mask_type=self.self_attn_mask_type
        )
        if self.add_pooler:
            self.pooler = Pooler(cfg)

    def __call__(self, token_ids, position_ids=None, attention_mask=None,
                 deterministic: bool = True, pooling_sequence_index: int = 0,
                 segment_ids=None):
        x = self.embedding(token_ids, position_ids,
                           deterministic=deterministic)
        hidden = self.encoder(x, attention_mask, deterministic=deterministic,
                              segment_ids=segment_ids)
        if self.add_pooler:
            pooled = self.pooler(hidden, pooling_sequence_index)
            return hidden, pooled
        return hidden
