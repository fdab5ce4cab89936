"""An ``afmoe`` decoder (sliding-window layers with rotary positions and
global layers without, a gated attention output, four norms a layer, a
dense layer then sparse ones beside a shared expert) under the causal
next-token objective through the normal Gluon path: the model zoo's
AfmoeForCausalLM, cast by amp.convert_hybrid_block (norm scales, the
router and its bias stay float32)."""
# at import: a program without the model fails here, before any weight is
# made
from mxnet_tpu.gluon.model_zoo.afmoe import afmoe

# config.json's own keys, passed on under their names
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "sliding_window", "num_dense_layers", "num_shared_experts",
        "route_norm", "route_scale", "score_func", "rms_norm_eps",
        "rope_theta", "rope_scaling", "mup_enabled", "tie_word_embeddings",
        "n_group", "topk_group", "num_expert_groups", "num_limited_groups")


def build(mx, cfg, weights, ctx):
    import jax.numpy as jnp

    from mxnet_tpu import amp
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = afmoe(
        num_experts=cfg["router_width"], ep_size=cfg["ep_size"],
        ep_rank=cfg["ep_rank"], remat=cfg["remat"],
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
