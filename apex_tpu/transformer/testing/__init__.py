"""Test harness models — reference ``apex/transformer/testing``."""

from apex_tpu.transformer.testing.standalone_transformer_lm import (
    AttentionKind,
    CoreAttention,
    Embedding,
    ExpertSpec,
    HybridParams,
    HybridSpec,
    ParallelAttention,
    ParallelMLP,
    ParallelTransformer,
    ParallelTransformerLayer,
    Pooler,
    TransformerConfig,
    TransformerLanguageModel,
    parallel_lm_logits,
)
from apex_tpu.transformer.testing.standalone_gpt import (
    GPTModel,
    gpt_loss,
    gpt_next_token_loss,
    init_gpt_layer_stack,
)
from apex_tpu.transformer.testing.standalone_bert import (
    BertModel,
    bert_extended_attention_mask,
)

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
    "Pooler",
    "TransformerLanguageModel",
    "parallel_lm_logits",
    "GPTModel",
    "gpt_loss",
    "gpt_next_token_loss",
    "init_gpt_layer_stack",
    "BertModel",
    "bert_extended_attention_mask",
]
