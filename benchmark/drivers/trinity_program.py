"""The program's side of the ``trinity-mini`` configuration: its
``TransformerConfig`` (a :class:`HybridSpec` of sliding-window and full
attention kinds with QK-norm and an output gate, sandwich norms, a dense and
an expert feed-forward with a shared expert) from the reference's sizes, and
the plain reference's seeded weights as the trainer's parameter tree.

The reference (benchmark code) makes the weights; the program only receives
them.  Both keep a linear weight ``[in, out]``, an expert's gate and up
matrices side by side and the same leaf names, so the map is by name and
copies nothing.
"""

import jax

from drivers.gpt_program import seed_key  # noqa: F401  (the drivers' one)


def transformer_config(sz: dict, dtype):
    """The program's configuration from the reference's sizes (which are the
    configuration file's): float32 parameters, matmul operands in
    ``dtype``."""
    from apex_tpu.transformer.testing import (
        AttentionKind, ExpertSpec, HybridSpec, TransformerConfig)

    def kind(name, window, rotary):
        return AttentionKind(
            name=name, num_heads=sz["heads"], kv_heads=sz["kv_heads"],
            k_dim=sz["head_dim"], v_dim=sz["head_dim"],
            rotary_dim=sz["head_dim"] if rotary else 0,
            rotary_base=sz["theta"], window=window, qk_norm=True, gate=True)

    layers = range(sz["layers"])
    hybrid = HybridSpec(
        kinds=(kind("full", None, False), kind("window", sz["window"], True)),
        layer_kinds=tuple(int(s) for s in sz["sliding"]),
        layer_experts=tuple(layer >= sz["dense_layers"] for layer in layers),
        experts=ExpertSpec(
            n_experts=sz["n_experts"], top_k=sz["top_k"],
            ffn_size=sz["expert_ffn"], held=tuple(sz["held"]),
            shared_experts=sz["shared"], route_scale=sz["route_scale"],
            route_eps=1e-20),
        sandwich_norm=True, embedding_multiplier=sz["embed_scale"])
    return TransformerConfig(
        hidden_size=sz["hidden"], num_layers=sz["layers"],
        num_attention_heads=sz["heads"], kv_channels=sz["head_dim"],
        ffn_hidden_size=sz["dense_ffn"],
        padded_vocab_size=sz["vocab_padded"], hidden_dropout=0.0,
        attention_dropout=0.0, layernorm_epsilon=sz["eps"],
        init_method_std=dict(sz["init"])["std"],
        position_embedding_type="rope", swiglu=True, tensor_axis=None,
        use_flash_attention=True, hybrid=hybrid, dtype=dtype)


def to_reference_names(params) -> dict:
    """A tree shaped like the program's ``HybridParams`` as the reference's
    dict of the same leaves."""
    return {"embedding": params.embedding, "head": params.head,
            "final_norm": params.final_norm,
            "layers": [dict(lp) for lp in params.layers]}


def weights_maker(template, reference, sz):
    """A jitted ``key -> weights``: the reference's seeded weights as the
    program's tree (``template``: its own freshly initialised parameters,
    whose leaves' names and shapes must be the reference's), made on the
    device in one call."""
    from apex_tpu.transformer.testing import HybridParams

    shapes = jax.tree_util.tree_map(lambda x: x.shape,
                                    to_reference_names(template))
    want = jax.tree_util.tree_map(
        lambda x: x.shape, jax.eval_shape(
            lambda k: reference.init_weights(k, sz), jax.random.PRNGKey(0)))
    if shapes != want:
        raise ValueError(f"the program's leaves {shapes} are not the "
                         f"reference's {want}")

    def make(key):
        w = reference.init_weights(key, sz)
        return HybridParams(embedding=w["embedding"],
                            layers=tuple(w["layers"]),
                            final_norm=w["final_norm"], head=w["head"])

    return jax.jit(make)
