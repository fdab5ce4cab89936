"""A ``deepseek_v3`` decoder (latent attention, a dense layer then sparse
ones with a shared expert) under the causal next-token objective through
the normal Gluon path: the model zoo's DeepseekV3ForCausalLM, cast by
amp.convert_hybrid_block (norm scales, the router and its bias stay
float32)."""
# at import: a program without the model fails here, before any weight is
# made
from mxnet_tpu.gluon.model_zoo.deepseek_v3 import deepseek_v3

# config.json's own keys, passed on under their names
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts",
        "first_k_dense_replace", "routed_scaling_factor", "norm_topk_prob",
        "scoring_func", "rope_theta", "rope_interleave", "rms_norm_eps",
        "q_lora_rank", "n_group", "topk_group")


def build(mx, cfg, weights, ctx):
    import jax.numpy as jnp

    from mxnet_tpu import amp
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = deepseek_v3(
        n_routed_experts=cfg["router_width"], ep_size=cfg["ep_size"],
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
