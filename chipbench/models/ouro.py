"""A looped decoder (model type ``ouro``: one stack of sandwich-normed
layers run ``total_ut_steps`` times on shared weights, an exit gate and
the expected loss over the exits) through the normal Gluon path: the model
zoo's OuroForCausalLM, cast by amp.convert_hybrid_block (norm scales and
the exit gate stay float32)."""
# at import: a program without the model fails here, before any weight is
# made
from mxnet_tpu.gluon.model_zoo.ouro import ouro

# config.json's own keys, passed on under their names
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "total_ut_steps", "rope_theta", "rms_norm_eps")


def build(mx, cfg, weights, ctx):
    import jax.numpy as jnp

    from mxnet_tpu import amp
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = ouro(entropy_beta=cfg["entropy_beta"], remat=cfg["remat"],
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
