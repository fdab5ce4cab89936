"""SDAR (model type ``sdar_moe``) under the block-diffusion objective
through the normal Gluon path: the model zoo's SDARForBlockDiffusion, cast
by amp.convert_hybrid_block (norm scales and the router stay float32)."""


def build(mx, cfg, weights, ctx):
    import jax.numpy as jnp

    from mxnet_tpu import amp
    from mxnet_tpu.gluon.model_zoo import sdar_moe
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = sdar_moe(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_units=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"],
        norm_topk_prob=cfg["norm_topk_prob"], ep_size=cfg["ep_size"],
        ep_rank=cfg["ep_rank"], rope_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"], remat=cfg["remat"])
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
    clean tokens, u and the blocks' noise levels."""
    return None, 3
