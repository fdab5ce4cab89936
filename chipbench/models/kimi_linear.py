"""A ``kimi_linear`` decoder (Kimi Delta Attention layers and latent
attention without positions, a dense layer then sparse ones beside a
shared expert) under the causal next-token objective through the normal
Gluon path: the model zoo's KimiLinearForCausalLM, cast by
amp.convert_hybrid_block (norm scales, the convolutions' taps, A_log,
dt_bias, the router and its bias stay float32)."""
# at import: a program without the model fails here, before any weight is
# made
from mxnet_tpu.gluon.model_zoo.kimi_linear import kimi_linear

# config.json's own keys, passed on under their names
KEYS = ("vocab_size", "hidden_size", "linear_attn_config",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_token",
        "num_shared_experts", "first_k_dense_replace",
        "routed_scaling_factor", "moe_renormalize",
        "moe_router_activation_func", "rms_norm_eps", "q_lora_rank",
        "num_expert_group", "topk_group", "rope_scaling",
        "num_nextn_predict_layers", "tie_word_embeddings", "mla_use_nope",
        "num_hidden_layers")


def build(mx, cfg, weights, ctx):
    import jax.numpy as jnp

    from mxnet_tpu import amp
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = kimi_linear(
        num_experts=cfg["router_width"], layers=cfg["layers_held"],
        ep_size=cfg["ep_size"], ep_rank=cfg["ep_rank"], remat=cfg["remat"],
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
