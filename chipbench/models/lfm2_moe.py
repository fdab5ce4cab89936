"""An ``lfm2_moe`` decoder (gated short convolutions and grouped-query
attention over a dense layer then sparse ones, tied embedding) under the
causal next-token objective through the normal Gluon path: the model
zoo's Lfm2MoeForCausalLM, cast by amp.convert_hybrid_block (norm scales,
the convolution's taps, the router and its bias stay float32)."""
# at import: a program without the model fails here, before any weight is
# made
from mxnet_tpu.gluon.model_zoo.lfm2_moe import lfm2_moe

# config.json's own keys, passed on under their names
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
        "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok", "num_dense_layers",
        "conv_L_cache", "conv_bias", "norm_eps", "norm_topk_prob",
        "routed_scaling_factor", "use_expert_bias", "tie_word_embeddings")


def build(mx, cfg, weights, ctx):
    import jax.numpy as jnp

    from mxnet_tpu import amp
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = lfm2_moe(
        num_experts=cfg["router_width"], ep_size=cfg["ep_size"],
        ep_rank=cfg["ep_rank"], remat=cfg["remat"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        **{k: cfg[k] for k in KEYS})
    net.initialize(ctx=ctx)
    params = net.collect_params()
    missing = sorted(set(params) ^ set(weights))
    if missing:
        raise KeyError(f"weights and net disagree on parameters: {missing}")
    for name, p in params.items():
        # a copy: the step donates its parameters' buffers
        p.set_data(NDArray(jnp.copy(weights[name])))
    if cfg["dtype"] != "float32":
        amp.convert_hybrid_block(net, target_dtype=cfg["dtype"])
    net.hybridize()
    return net


def loss(mx, cfg):
    """The net returns the loss of each sequence itself; it takes the
    tokens."""
    return None, 1
