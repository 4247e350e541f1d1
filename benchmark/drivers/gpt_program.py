"""What the training and serving drivers share: the program's
``TransformerConfig`` from a configuration file, and the seeded weights of
the plain reference laid out as the program's parameter tree.

The weights are made by the reference's ``init_weights`` (benchmark code);
the program only receives them.  The mapping below is the program's
parameter tree as ``build_gpt_3d``'s init returns it: per-layer leaves
stacked ``[vpp, pp, ...]``, linear kernels ``[out, in]``, fused QKV
head-major.
"""

import jax
import jax.numpy as jnp

# program leaf (jax.tree_util.keystr of its path) -> reference weight
PROGRAM_LEAVES = {
    ".embedding['word_embeddings']['embedding']": "wte",
    ".embedding['position_embeddings']['embedding']": "wpe",
    ".final_ln['scale']": "lnf_g",
    ".final_ln['bias']": "lnf_b",
    ".layers['input_layernorm']['scale']": "ln1_g",
    ".layers['input_layernorm']['bias']": "ln1_b",
    ".layers['self_attention']['query_key_value']['kernel']": "qkv_w",
    ".layers['self_attention']['query_key_value']['bias']": "qkv_b",
    ".layers['self_attention']['dense']['kernel']": "proj_w",
    ".layers['self_attention']['dense']['bias']": "proj_b",
    ".layers['post_attention_layernorm']['scale']": "ln2_g",
    ".layers['post_attention_layernorm']['bias']": "ln2_b",
    ".layers['mlp']['dense_h_to_4h']['kernel']": "fc_w",
    ".layers['mlp']['dense_h_to_4h']['bias']": "fc_b",
    ".layers['mlp']['dense_4h_to_h']['kernel']": "out_w",
    ".layers['mlp']['dense_4h_to_h']['bias']": "out_b",
}


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def transformer_config(config: dict, tp: int, sequence_parallel: bool):
    """The program's configuration for a Hugging Face GPT-2 ``config.json``:
    bf16 compute over fp32 parameters, flash attention."""
    from apex_tpu.transformer.testing import TransformerConfig

    act = config["activation_function"]
    if act not in ("gelu_new", "gelu"):
        raise ValueError(f"no GeLU of the program matches {act!r}")
    if any(config[k] for k in ("attn_pdrop", "embd_pdrop", "resid_pdrop")):
        # build_gpt_3d and the engine apply their layers deterministically:
        # a rate stated here would be applied by no step
        raise ValueError("the program's trainer and server apply no dropout")
    return TransformerConfig(
        hidden_size=config["n_embd"], num_layers=config["n_layer"],
        num_attention_heads=config["n_head"],
        ffn_hidden_size=config.get("n_inner") or 4 * config["n_embd"],
        padded_vocab_size=config["assumed"]["padded_vocab_size"],
        max_position_embeddings=config["n_positions"],
        hidden_dropout=config["resid_pdrop"],
        attention_dropout=config["attn_pdrop"],
        init_method_std=config["initializer_range"],
        layernorm_epsilon=config["layer_norm_epsilon"],
        bias_gelu_fusion=act == "gelu_new",   # tanh GeLU; "gelu" is erf
        use_flash_attention=True, dtype=jnp.bfloat16,
        tensor_axis="tp" if tp > 1 else None,
        sequence_parallel=sequence_parallel)


def _paths(tree):
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def to_reference_names(tree) -> dict:
    """A program parameter-shaped tree as ``{reference name: leaf}``."""
    leaves = jax.tree_util.tree_leaves(tree)
    return {PROGRAM_LEAVES[p]: x for p, x in zip(_paths(tree), leaves)}


def weights_maker(template, reference, sz):
    """A jitted ``key -> weights``: the reference's seeded weights in the
    shapes, tree and shardings of ``template`` (the program's own freshly
    initialised parameters), made on the device in one call."""
    treedef = jax.tree_util.tree_structure(template)
    names = [PROGRAM_LEAVES[p] for p in _paths(template)]
    leaves = jax.tree_util.tree_leaves(template)
    shapes = [x.shape for x in leaves]
    shardings = jax.tree_util.tree_unflatten(
        treedef, [x.sharding for x in leaves])

    def make(key):
        w = reference.init_weights(key, sz)
        return jax.tree_util.tree_unflatten(
            treedef, [w[n].reshape(s) for n, s in zip(names, shapes)])

    return jax.jit(make, out_shardings=shardings)
