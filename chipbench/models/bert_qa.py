"""BERT + SQuAD span head through the normal Gluon path: the model zoo's
BERTModel and BERTForQA, cast by amp.convert_hybrid_block."""


def build(mx, cfg, weights, ctx):
    import jax.numpy as jnp

    from mxnet_tpu import amp
    from mxnet_tpu.gluon.model_zoo.bert import BERTForQA, BERTModel
    from mxnet_tpu.ndarray.ndarray import NDArray

    bert = BERTModel(vocab_size=cfg["vocab_size"],
                     token_type_vocab_size=cfg["type_vocab_size"],
                     max_length=cfg["max_length"], units=cfg["units"],
                     hidden_size=cfg["hidden_size"],
                     num_layers=cfg["num_layers"],
                     num_heads=cfg["num_heads"], dropout=cfg["dropout"],
                     use_pooler=False, use_decoder=False)
    net = BERTForQA(bert, dropout=cfg["dropout"])
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
    """SQuAD span loss per sample; the net takes tokens and segments."""
    from mxnet_tpu import gluon

    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def span_loss(out, start, end):
        return ce(out[0], start) + ce(out[1], end)

    return span_loss, 2
