"""The program's side of the ``glm-5`` configuration: its
``TransformerConfig`` (a :class:`HybridSpec` of one sparse latent attention
kind, an indexer beside the latent attention, over a dense and an expert
feed-forward with sigmoid routing, a selection bias, normalised weights and
one shared expert) from the configuration file, and the plain reference's
seeded weights as the program's parameter tree.

The reference (benchmark code) makes the weights; the program only receives
them.  Both keep a linear weight ``[in, out]``, ``kv_b_proj`` as its two
halves per head and an expert's gate and up matrices side by side, so the
map is by name and copies nothing.
"""

from drivers.gpt_program import seed_key  # noqa: F401  (the drivers' one)

# the reference's per-layer names the program takes, by feed-forward kind
ATTENTION = ("norm1", "norm2", "wq_a", "q_a_norm", "wq_b", "wkv_a",
             "kv_a_norm", "w_uk", "w_uv", "wo", "wq_idx", "wk_idx",
             "k_idx_norm", "k_idx_norm_bias", "w_idx")
DENSE = ("ffn_gate_up", "ffn_down")
EXPERTS = ("router", "router_bias", "experts_gate_up", "experts_down",
           "shared_gate_up", "shared_down")


def transformer_config(sz: dict, dtype):
    """The program's configuration from the reference's sizes (which are the
    configuration file's): parameters, compute and cache in ``dtype``."""
    from apex_tpu.transformer.testing import (
        AttentionKind, ExpertSpec, HybridSpec, TransformerConfig)

    k = sz["kinds"][0]
    kind = AttentionKind(
        name="sparse_latent", num_heads=k["heads"], kv_heads=1,
        k_dim=k["k_dim"], v_dim=k["v_dim"], rotary_dim=k["rope"],
        rotary_base=k["theta"], latent_rank=k["kv_rank"],
        q_rank=k["q_rank"], nope_dim=k["nope"],
        index_heads=k["index_heads"], index_dim=k["index_dim"],
        index_rope_dim=k["index_rope"], index_topk=k["index_topk"])
    hybrid = HybridSpec(
        kinds=(kind,), layer_kinds=tuple(sz["pattern"]),
        layer_experts=tuple(bool(e) for e in sz["experts"]),
        experts=ExpertSpec(
            n_experts=sz["n_experts"], top_k=sz["top_k"],
            ffn_size=sz["expert_ffn"], held=tuple(sz["held"]),
            shared_experts=sz["shared"], route_scale=sz["route_scale"],
            scoring="sigmoid", normalize=True))
    return TransformerConfig(
        hidden_size=sz["hidden"], num_layers=sz["layers"],
        num_attention_heads=kind.num_heads, kv_channels=kind.k_dim,
        ffn_hidden_size=sz["dense_ffn"], padded_vocab_size=sz["vocab"],
        hidden_dropout=0.0, attention_dropout=0.0,
        layernorm_epsilon=sz["eps"], position_embedding_type="rope",
        swiglu=True, tensor_axis=None, hybrid=hybrid, dtype=dtype,
        param_dtype=dtype)


def program_params(weights: dict, sz: dict, dtype):
    """The reference's weights as the program's ``HybridParams``."""
    from apex_tpu.serving import HybridParams

    layers = []
    for layer, lw in enumerate(weights["layers"]):
        names = ATTENTION + (EXPERTS if sz["experts"][layer] else DENSE)
        if sorted(names) != sorted(lw):
            raise ValueError(f"layer {layer} holds {sorted(lw)}, the "
                             f"program takes {sorted(names)}")
        layers.append({k: lw[k].astype(dtype) for k in names})
    return HybridParams(
        embedding=weights["embedding"].astype(dtype), layers=tuple(layers),
        final_norm=weights["final_norm"].astype(dtype),
        head=weights["head"].astype(dtype))
