"""Standalone GPT — reference ``apex/transformer/testing/standalone_gpt.py``.

``GPTModel`` (reference ``:45``, wrapping ``TransformerLanguageModel`` with a
causal mask and ``post_language_model_processing``: logits against the shared
embedding + vocab-parallel cross entropy) plus the pipelined-stage helpers
the SPMD schedules need (see
:mod:`apex_tpu.transformer.pipeline_parallel.schedules` stage-homogeneity
note).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.ops.softmax import AttnMaskType
from apex_tpu.parallel.collectives import bound_axis_size
from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
from apex_tpu.transformer.tensor_parallel import vocab_parallel_cross_entropy
from apex_tpu.transformer.testing.standalone_transformer_lm import (
    ParallelTransformerLayer,
    TransformerConfig,
    TransformerLanguageModel,
    parallel_lm_logits,
)

__all__ = ["GPTModel", "gpt_loss", "gpt_next_token_loss",
           "init_gpt_layer_stack"]


class GPTModel(nn.Module):
    """GPT LM: causal ``TransformerLanguageModel`` + embedding-tied logits.

    Forward returns per-token next-token loss ``[b, s-1]`` when
    ``labels`` is given, else logits ``[s, b, vocab(/tp)]``.

    Deliberate API divergence from the reference
    ``post_language_model_processing``: there the *data pipeline* pre-shifts
    labels; this framework has no mandatory data pipeline, so ``labels``
    are the **raw tokens** and the shift happens centrally in
    :func:`gpt_next_token_loss` — every caller (tests, 3D trainer)
    gets the same non-degenerate objective.
    """

    config: TransformerConfig

    def setup(self):
        self.language_model = TransformerLanguageModel(
            self.config, self_attn_mask_type=AttnMaskType.causal
        )

    def __call__(self, input_ids, position_ids=None, attention_mask=None,
                 labels=None, deterministic: bool = True):
        cfg = self.config
        hidden = self.language_model(input_ids, position_ids, attention_mask,
                                     deterministic=deterministic)
        logits = parallel_lm_logits(
            hidden, self.language_model.embedding.word_embeddings, cfg
        )
        if labels is None:
            return logits
        return gpt_next_token_loss(logits, labels, cfg)


def gpt_next_token_loss(logits, tokens, config: TransformerConfig):
    """Shifted LM objective: position ``t`` predicts token ``t+1``.

    ``logits [s, b, v(/tp)]`` (full sequence — ``parallel_lm_logits`` has
    already gathered SP shards), ``tokens [b, s]`` raw; returns ``[b, s-1]``
    per-token losses.  Without the shift the objective is trivially
    learnable through the tied embedding (round-1 ADVICE).
    """
    return gpt_loss(logits[:-1], tokens[:, 1:], config)


def gpt_loss(logits, labels, config: TransformerConfig):
    """Per-token LM loss ``[b, s]`` from ``[s, b, v(/tp)]`` logits.

    Vocab-parallel CE under tensor parallelism
    (``tensor_parallel/cross_entropy.py:23-131``), fused max+logsumexp CE
    (``apex/contrib/xentropy``) otherwise.

    HBM-bandwidth note (the loss head is ~27 % of GPT-124M step FLOPs and
    its logits tensor is ~0.8 GB at batch 8 x 1024): the big ``[s, b,
    v]`` tensor is flattened **in its native s-major order** — only the
    int32 labels and the fp32 per-token losses (both [b, s], KBs) get
    transposed — and half logits enter the CE kernel in their storage
    dtype (``half_to_float=True``; the kernel upcasts row-wise in fp32
    and keeps original-dtype residuals, ``ops/xentropy.py``).  Both are
    value-identical to transposing/upcasting first: the upcast point
    commutes with the row reductions, and row order commutes with a
    per-row loss."""
    v = logits.shape[-1]
    flat = logits.reshape(-1, v)            # [s*b, v] — no big transpose
    labels_sb = labels.T.reshape(-1)        # [b,s] -> [s*b] row order
    world = bound_axis_size(config.tensor_axis)
    if world > 1:
        loss = vocab_parallel_cross_entropy(flat, labels_sb,
                                            axis=config.tensor_axis)
    else:
        loss = softmax_cross_entropy_loss(
            flat,
            labels_sb,
            padding_idx=-1,  # no padding label in LM loss
            half_to_float=True,  # fp32 losses, half logits stay half
        )
    return loss.reshape(logits.shape[0], labels.shape[0]).T  # -> [b, s]


def init_gpt_layer_stack(key, config: TransformerConfig, sample_hidden,
                         sample_mask=None):
    """Init per-layer params for the pipelined GPT.

    Returns ``(make_stage_fn, per_layer_params_list)``.
    ``make_stage_fn(mask=None, deterministic=True, rngs=None)`` builds the
    homogeneous ``stage_fn(layer_params, x)`` the rotation schedule consumes
    — mask/dropout mode are bound per *call*, not frozen at init.

    The pipelined decomposition: embedding and the loss head run outside the
    rotation (replicated over ``pp``); the ``num_layers`` homogeneous
    :class:`ParallelTransformerLayer` blocks are the virtual stages.
    """
    cfg = config
    layer = ParallelTransformerLayer(
        cfg, self_attn_mask_type=AttnMaskType.causal
    )
    keys = jax.random.split(key, cfg.num_layers)
    per_layer = [
        layer.init(k, sample_hidden, sample_mask)["params"] for k in keys
    ]

    def make_stage_fn(mask=None, deterministic: bool = True, rngs=None):
        def stage_fn(layer_params, x):
            return layer.apply({"params": layer_params}, x, mask,
                               deterministic=deterministic, rngs=rngs)
        return stage_fn

    return make_stage_fn, per_layer
