"""The program's side of the ``mimo-v2-flash`` configuration: its
``TransformerConfig`` (a :class:`HybridSpec` of full and sliding-window
attention kinds over a dense and an expert feed-forward) from the
configuration file, and the plain reference's seeded weights as the
program's parameter tree.

The reference (benchmark code) makes the weights; the program only receives
them.  Both keep a linear weight ``[in, out]`` and an expert's gate and up
matrices side by side, so the map is by name and copies nothing.
"""

from drivers.gpt_program import seed_key  # noqa: F401  (the drivers' one)

# the reference's per-layer names the program takes, by feed-forward kind
ATTENTION = ("norm1", "norm2", "wq", "wk", "wv", "wo")
DENSE = ("ffn_gate_up", "ffn_down")
EXPERTS = ("router", "router_bias", "experts_gate_up", "experts_down")


def transformer_config(sz: dict, dtype):
    """The program's configuration from the reference's sizes (which are the
    configuration file's): parameters, compute and cache in ``dtype``."""
    from apex_tpu.transformer.testing import (
        AttentionKind, ExpertSpec, HybridSpec, TransformerConfig)

    kinds = tuple(
        AttentionKind(name=name, num_heads=k["heads"],
                      kv_heads=k["kv_heads"], k_dim=k["k_dim"],
                      v_dim=k["v_dim"], rotary_dim=sz["rotary"],
                      rotary_base=k["theta"], window=k["window"],
                      sink=k["sink"])
        for name, k in zip(("full", "window"), sz["kinds"]))
    hybrid = HybridSpec(
        kinds=kinds, layer_kinds=tuple(sz["pattern"]),
        layer_experts=tuple(bool(e) for e in sz["experts"]),
        experts=ExpertSpec(n_experts=sz["n_experts"], top_k=sz["top_k"],
                           ffn_size=sz["expert_ffn"], held=tuple(sz["held"])),
        value_scale=sz["value_scale"])
    return TransformerConfig(
        hidden_size=sz["hidden"], num_layers=sz["layers"],
        num_attention_heads=kinds[0].num_heads,
        kv_channels=kinds[0].k_dim, ffn_hidden_size=sz["dense_ffn"],
        padded_vocab_size=sz["vocab"], hidden_dropout=0.0,
        attention_dropout=0.0, layernorm_epsilon=sz["eps"],
        position_embedding_type="rope", swiglu=True, tensor_axis=None,
        hybrid=hybrid, dtype=dtype, param_dtype=dtype)


def program_params(weights: dict, sz: dict, dtype):
    """The reference's weights as the program's ``HybridParams``."""
    from apex_tpu.serving import HybridParams

    layers = []
    for layer, lw in enumerate(weights["layers"]):
        names = ATTENTION + (EXPERTS if sz["experts"][layer] else DENSE)
        if sz["kinds"][sz["pattern"][layer]]["sink"]:
            names += ("sinks",)
        if sorted(names) != sorted(lw):
            raise ValueError(f"layer {layer} holds {sorted(lw)}, the "
                             f"program takes {sorted(names)}")
        layers.append({k: lw[k].astype(dtype) for k in names})
    return HybridParams(
        embedding=weights["embedding"].astype(dtype), layers=tuple(layers),
        final_norm=weights["final_norm"].astype(dtype),
        head=weights["head"].astype(dtype))
