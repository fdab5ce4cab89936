"""Plain reference: BERT encoder (Devlin et al. 2018, arXiv:1810.04805)
with the SQuAD span head, in jax.numpy, float32, matmul precision
``highest``.

A dict of arrays keyed by the Gluon parameter names goes in, start and
end logits come out.  As published: post-LayerNorm layers, learned
position embeddings, exact (erf) GELU, softmax attention scaled by
1/sqrt(head size), no attention mask (every sequence fills its length).
The query, key and value projections are one (3*units, units) matrix, as
the model zoo stores them: rows [0, units) are the queries.  Departures,
both the model zoo's: LayerNorm's epsilon is the configuration's (1e-5,
the paper's code has 1e-12), and the loss is the SUM of the start and
end cross-entropies per sample (run_squad.py halves it).  Dropout is not
in the reference: the configuration sets it to 0.

``precision``: as in resnet_v1.py.
"""
import functools

import jax
import jax.numpy as jnp

from .precision import HI, _q


def param_specs(cfg):
    """(name, shape, kind, arg, low) by Gluon name.  N(0, 0.02) as the
    paper initialises, biases and LayerNorm shifts too, so that leaving
    one out shows; LayerNorm scales U(0.9, 1.1)."""
    d, ff = cfg["units"], cfg["hidden_size"]
    specs = []

    def mat(name, shape):
        specs.append((name, shape, "normal", 0.02, True))

    def ln(name):
        specs.append((name + ".gamma", (d,), "uniform", (0.9, 1.1), False))
        specs.append((name + ".beta", (d,), "normal", 0.02, False))

    mat("bert.word_embed.weight", (cfg["vocab_size"], d))
    mat("bert.token_type_embed.weight", (cfg["type_vocab_size"], d))
    mat("bert.position_embed.weight", (cfg["max_length"], d))
    ln("bert.embed_layer_norm")
    for i in range(cfg["num_layers"]):
        p = f"bert.encoder.layers.{i}."
        mat(p + "attention.qkv.weight", (3 * d, d))
        mat(p + "attention.qkv.bias", (3 * d,))
        mat(p + "attention.out_proj.weight", (d, d))
        mat(p + "attention.out_proj.bias", (d,))
        mat(p + "ffn.ffn_1.weight", (ff, d))
        mat(p + "ffn.ffn_1.bias", (ff,))
        mat(p + "ffn.ffn_2.weight", (d, ff))
        mat(p + "ffn.ffn_2.bias", (d,))
        ln(p + "layer_norm_att")
        ln(p + "layer_norm_ffn")
    mat("span_classifier.weight", (2, d))
    mat("span_classifier.bias", (2,))
    return tuple(specs)


def input_specs(cfg, batch):
    """Token ids, segment ids, start and end positions."""
    s = cfg["seq"]
    return (((batch, s), "randint", 0, cfg["vocab_size"]),
            ((batch, s), "randint", 0, cfg["type_vocab_size"]),
            ((batch,), "randint", 0, s),
            ((batch,), "randint", 0, s))


def trainable(name):
    return True


def _dense(x, p, name, precision):
    return jnp.matmul(_q(x, precision), _q(p[name + ".weight"], precision).T,
                      precision=HI) + p[name + ".bias"]


def _ln(x, p, name, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p[name + ".gamma"] \
        + p[name + ".beta"]


def _layer(x, p, prefix, heads, eps, precision):
    b, s, d = x.shape
    hd = d // heads
    qkv = _dense(x, p, prefix + "attention.qkv", precision)
    qkv = qkv.reshape(b, s, 3, heads, hd)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", _q(q, precision),
                        _q(k, precision), precision=HI) / hd ** 0.5
    att = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", _q(att, precision), _q(v, precision),
                     precision=HI)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = _ln(x + _dense(ctx, p, prefix + "attention.out_proj", precision),
            p, prefix + "layer_norm_att", eps)
    h = jax.nn.gelu(_dense(x, p, prefix + "ffn.ffn_1", precision),
                    approximate=False)
    return _ln(x + _dense(h, p, prefix + "ffn.ffn_2", precision),
               p, prefix + "layer_norm_ffn", eps)


def forward(cfg, p, tokens, segments, precision="float32"):
    """(start logits, end logits), each (batch, seq), float32."""
    eps = cfg["layer_norm_eps"]
    s = tokens.shape[1]
    x = (p["bert.word_embed.weight"][tokens]
         + p["bert.token_type_embed.weight"][segments]
         + p["bert.position_embed.weight"][:s][None])
    x = _ln(x, p, "bert.embed_layer_norm", eps)
    for i in range(cfg["num_layers"]):
        # recompute inside each layer on the way back, so that the timed
        # batch fits in float32
        layer = jax.checkpoint(functools.partial(
            _layer, prefix=f"bert.encoder.layers.{i}.",
            heads=cfg["num_heads"], eps=eps, precision=precision))
        x = layer(x, p)
    logits = _dense(x, p, "span_classifier", precision)
    return logits[:, :, 0], logits[:, :, 1]


def _ce(logits, label):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, label[:, None].astype(jnp.int32),
                                axis=-1)[:, 0]


def per_sample_loss(cfg, p, batch, precision="float32"):
    tokens, segments, start, end = batch
    s_log, e_log = forward(cfg, p, tokens, segments, precision)
    return _ce(s_log, start) + _ce(e_log, end)


def forward_flops(cfg):
    import flops

    return flops.bert_forward(cfg["num_layers"], cfg["units"],
                              cfg["hidden_size"], cfg["seq"])
