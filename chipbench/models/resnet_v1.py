"""ResNet v1 through the normal Gluon path: the model zoo's network,
NHWC, cast to the configuration's type by amp.convert_hybrid_block."""


def build(mx, cfg, weights, ctx):
    """The hybridized net on ``ctx`` holding ``weights`` ({gluon name:
    float32 array}) in the configuration's type."""
    from mxnet_tpu import amp
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = get_resnet(1, cfg["num_layers"], classes=cfg["classes"],
                     layout=cfg["layout"],
                     thumbnail=cfg.get("thumbnail", False))
    net.initialize(ctx=ctx)
    params = net.collect_params()
    missing = sorted(set(params) ^ set(weights))
    if missing:
        raise KeyError(f"weights and net disagree on parameters: {missing}")
    import jax.numpy as jnp

    for name, p in params.items():
        # a copy: the step donates its parameters' buffers, and the
        # benchmark's own arrays must outlive it for the reference
        p.set_data(NDArray(jnp.copy(weights[name])))
    if cfg["dtype"] != "float32":
        amp.convert_hybrid_block(net, target_dtype=cfg["dtype"])
    net.hybridize()
    return net


def loss(mx, cfg):
    """(loss_fn(out, *labels) -> per-sample loss, number of net inputs)."""
    from mxnet_tpu import gluon

    return gluon.loss.SoftmaxCrossEntropyLoss(), 1
