"""Plain reference: a decoder of model type ``lfm2_moe``
(LiquidAI/LFM2-24B-A2B) under the causal next-token objective, in
jax.numpy, float32, matmul precision ``highest``.  Imports nothing of the
program.

A dict of arrays keyed by the Gluon parameter names goes in, the loss of
each sequence comes out.  Batch element: tokens in [0, vocab_size)^S.
Pre-norm residual layers, h = x + Op(RMSNorm(x)), y = h +
FF(RMSNorm(h)); every norm an RMSNorm with a learned scale, ``norm_eps``,
float32; no biases anywhere.

* Op of a layer whose ``layer_types`` entry is ``"conv"`` — the gated
  short convolution, D = ``hidden_size``, L = ``conv_L_cache``:
  [B ; C ; x~] = W_in z (three equal chunks in this order); u = B * x~;
  c_t = sum over j < L of w[:, j] * u_{t - (L - 1) + j} with u_s = 0 for
  s < 0; Op = W_out (C * c).
* Op of a ``"full_attention"`` layer: q, k, v = W_q z, W_k z, W_v z as H /
  KV / KV heads of D / H; RMSNorm over each head's width of q and of k
  (one scale vector for all query heads, one for all key heads);
  rotate-half rotary positions over the whole head, ``rope_theta``,
  positions 0 .. S - 1; query head h reads key-value head h // (H / KV);
  o_h = softmax_causal(q_h k_h^T / sqrt(D / H)) v_h; Op = W_o [o_1 .. o_H].
* FF of the first ``num_dense_layers`` layers: one gated MLP
  W_down(silu(W_gate z) * W_up z), ``intermediate_size`` wide.
* FF of every other layer: s = sigmoid(W_r z) over the router's full
  width; S = the ``num_experts_per_tok`` largest of s + b (b the
  router's bias, ``use_expert_bias``); g_e = scaling * s_e / (sum_S s +
  1e-6) for e in S (``norm_topk_prob``) — the bias selects and never
  weighs, and no gradient reaches it; out = sum over e in S that are held
  of g_e * E_e(z), every E a gated MLP ``moe_intermediate_size`` wide.
* Head and loss: logits = W_embed RMSNorm(y) over the rows held — the
  embedding's own matrix (``tie_word_embeddings``), else a head of its
  own; loss of a sequence = (1 / (S - 1)) sum over i < S - 1 of
  CE(logits_i, tokens_{i+1}).

Departures, all of them the deployment's cut (the configuration file
states it): the chip holds ``num_experts`` of the router's
``router_width`` experts, from ``ep_rank * num_experts`` on, what the
absent experts would add is left out and the partial result goes on to
the next layer; the vocabulary is the slice of ``vocab_size`` rows.  The
bias is a seeded constant (config.json gives no update speed).  How it is
computed, not what: attention by blocks of queries, the head by blocks of
positions and each layer under ``jax.checkpoint`` so that 2 x 8192
positions fit; every held expert is applied to every token and weighted
by its gate (zero where it was not chosen), so the reference has no
routing machinery to share a fault with.

``precision``: as in resnet_v1.py — the operands of every matrix product
whose weights the configuration keeps in ``dtype`` are rounded to that
type; the router and the convolution's taps, which the configuration
keeps in float32, are not.
"""
import functools

import jax
import jax.numpy as jnp

from .precision import HI, _q

QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
HEAD_BLOCK = 512
NOT_TRAINED = ("running_load", "router_bias")
HEAD = "lm_head.weight"
EMBED = "model.embed_tokens.weight"


def _layers(cfg):
    """(prefix, operator is a convolution, feed-forward is dense) of each
    layer."""
    return [(f"model.layers.{i}.", kind == "conv",
             i < cfg["num_dense_layers"])
            for i, kind in enumerate(cfg["layer_types"])]


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_specs(cfg):
    """(name, shape, kind, arg, low) by Gluon name.  Matrices N(0, 0.02),
    rounded to the configuration's type except the router and the
    convolution's taps (float32 in the program too); the router's bias
    N(0, ``router_bias_std``), float32; norm scales U(0.9, 1.1)."""
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held, v = cfg["moe_intermediate_size"], cfg["num_experts"], \
        cfg["vocab_size"]
    specs = []

    def mat(name, shape, low=True):
        specs.append((name, shape, "normal", 0.02, low))

    def scale(name, n):
        specs.append((name + ".gamma", (n,), "uniform", (0.9, 1.1), False))

    mat(EMBED, (v, d))
    for p, conv, dense in _layers(cfg):
        scale(p + "operator_norm", d)
        if conv:
            mat(p + "conv.in_proj.weight", (3 * d, d))
            mat(p + "conv.conv_taps", (d, cfg["conv_L_cache"]), low=False)
            mat(p + "conv.out_proj.weight", (d, d))
        else:
            mat(p + "self_attn.q_proj.weight", (h * hd, d))
            mat(p + "self_attn.k_proj.weight", (kv * hd, d))
            mat(p + "self_attn.v_proj.weight", (kv * hd, d))
            mat(p + "self_attn.o_proj.weight", (d, h * hd))
            scale(p + "self_attn.q_norm", hd)
            scale(p + "self_attn.k_norm", hd)
        scale(p + "ffn_norm", d)
        ff = p + "feed_forward."
        if dense:
            for m, shape in (("gate", (cfg["intermediate_size"], d)),
                             ("up", (cfg["intermediate_size"], d)),
                             ("down", (d, cfg["intermediate_size"]))):
                mat(ff + m + "_proj.weight", shape)
            continue
        mat(ff + "router", (cfg["router_width"], d), low=False)
        specs.append((ff + "router_bias", (cfg["router_width"],), "normal",
                      cfg["router_bias_std"], False))
        mat(ff + "gate_proj", (held, d, f))
        mat(ff + "up_proj", (held, d, f))
        mat(ff + "down_proj", (held, f, d))
        # the layer's counters: state of the program, not of the model
        specs.append((ff + "running_load", (3,), "const", 0.0, False))
    scale("model.embedding_norm", d)
    if not cfg["tie_word_embeddings"]:
        mat(HEAD, (v, d))
    return tuple(specs)


def input_specs(cfg, batch):
    """Token ids drawn uniformly from the rows held."""
    return (((batch, cfg["seq"]), "randint", 0, cfg["vocab_size"]),)


def trainable(name):
    return not name.endswith(NOT_TRAINED)


def _mm(x, w, precision):
    """x (..., in) times w (out, in), as a Dense layer stores it."""
    return jnp.matmul(_q(x, precision), _q(w, precision).T, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, positions, theta):
    """x: (b, heads, s, width), rotate-half."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def short_conv(p, prefix, z, precision):
    """The gated short convolution of z (b, s, d).  The sum over the taps
    is written out as L shifted arrays: with w the (d, L) taps it equals
    PyTorch's ``Conv1d(d, d, L, groups=d, padding=L - 1, bias=False)``
    with weight w[:, None, :] applied to (B * x~) as (b, d, s) and cut to
    its first s outputs."""
    s = z.shape[1]
    w = p[prefix + "conv.conv_taps"]
    taps = w.shape[1]
    b_, c_, x_ = jnp.split(_mm(z, p[prefix + "conv.in_proj.weight"],
                               precision), 3, axis=-1)
    u = jnp.pad(b_ * x_, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w[:, j] * u[:, j:j + s] for j in range(taps))
    return _mm(c_ * conv, p[prefix + "conv.out_proj.weight"], precision)


def _attention(q, k, v, precision):
    """q: (b, heads, s, w); k, v: (b, kv heads, s, w).  One block of
    queries at a time against all keys, key j visible to query i iff
    j <= i; the query heads of a group read their key-value head."""
    b, h, s, w = q.shape
    kv = k.shape[1]
    q = q.reshape(b, kv, h // kv, s, w)
    k_pos = jnp.arange(s)
    blk = min(QUERY_BLOCK, s)
    kq, vq = _q(k, precision), _q(v, precision)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, axis=3)
        scores = jnp.einsum("bkgqd,bksd->bkgqs", _q(qb, precision), kq,
                            precision=HI) / w ** 0.5
        keep = k_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        att = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", _q(att, precision), vq,
                          precision=HI)

    out = jax.lax.map(block, jnp.arange(0, s, blk))  # (blocks, b, kv, g, blk, w)
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, s, w)


def attention(cfg, p, prefix, z, positions, precision):
    b, s, _ = z.shape
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        _head_dim(cfg)
    theta = float(cfg["rope_parameters"]["rope_theta"])

    def heads(name, n, norm):
        t = _mm(z, p[prefix + f"self_attn.{name}_proj.weight"],
                precision).reshape(b, s, n, hd)
        if norm:
            t = _rms(t, p[prefix + f"self_attn.{name}_norm.gamma"],
                     cfg["norm_eps"])
        t = t.transpose(0, 2, 1, 3)
        return _rope(t, positions, theta) if norm else t

    ctx = _attention(heads("q", h, True), heads("k", kv, True),
                     heads("v", kv, False), precision)
    return _mm(ctx.transpose(0, 2, 1, 3).reshape(b, s, h * hd),
               p[prefix + "self_attn.o_proj.weight"], precision)


def _mlp(p, prefix, z, precision):
    mid = jax.nn.silu(_mm(z, p[prefix + "gate_proj.weight"], precision)) \
        * _mm(z, p[prefix + "up_proj.weight"], precision)
    return _mm(mid, p[prefix + "down_proj.weight"], precision)


def route(cfg, p, prefix, z):
    """(gates (n, k), experts (n, k)) of the tokens z (n, d): float32."""
    logits = jnp.matmul(z, p[prefix + "feed_forward.router"].T,
                        precision=HI)
    s = jax.nn.sigmoid(logits)
    pick = s
    if cfg["use_expert_bias"]:
        pick = s + jax.lax.stop_gradient(
            p[prefix + "feed_forward.router_bias"])
    _, experts = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(s, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6)
    return gates * cfg["routed_scaling_factor"], experts


def routed(cfg, p, prefix, z, precision):
    """The held experts' part of the layer for the tokens z (n, d): every
    held expert on every token, weighted by its gate (zero where it was
    not chosen), one block of tokens at a time."""
    gates, experts = route(cfg, p, prefix, z)
    first = cfg["ep_rank"] * cfg["num_experts"]
    held = first + jnp.arange(cfg["num_experts"])
    n, d = z.shape
    blk = min(TOKEN_BLOCK, n)
    wg, wu, wd = (_q(p[prefix + f"feed_forward.{m}_proj"], precision)
                  for m in ("gate", "up", "down"))

    @jax.checkpoint
    def block(start):
        zb = _q(jax.lax.dynamic_slice_in_dim(z, start, blk), precision)
        gb = jax.lax.dynamic_slice_in_dim(gates, start, blk)
        eb = jax.lax.dynamic_slice_in_dim(experts, start, blk)
        # (held, blk): gate of each held expert for each token
        gate_of = jnp.sum(jnp.where(eb[None] == held[:, None, None],
                                    gb[None], 0.0), axis=-1)
        mid = jax.nn.silu(jnp.einsum("nd,edf->enf", zb, wg, precision=HI)) \
            * jnp.einsum("nd,edf->enf", zb, wu, precision=HI)
        # sum over experts and inner width in one product
        return jnp.einsum("enf,efd->nd",
                          _q(mid, precision) * gate_of[:, :, None], wd,
                          precision=HI)

    return jax.lax.map(block, jnp.arange(0, n, blk)).reshape(n, d)


def _layer(x, p, positions, *, cfg, prefix, conv, dense, precision,
           chosen=None):
    b, s, d = x.shape
    eps = cfg["norm_eps"]
    z = _rms(x, p[prefix + "operator_norm.gamma"], eps)
    x = x + (short_conv(p, prefix, z, precision) if conv
             else attention(cfg, p, prefix, z, positions, precision))
    z = _rms(x, p[prefix + "ffn_norm.gamma"], eps)
    if dense:
        return x + _mlp(p, prefix + "feed_forward.", z, precision)
    flat = z.reshape(b * s, d)
    if chosen is not None:
        chosen.append(route(cfg, p, prefix, flat)[1])
    return x + routed(cfg, p, prefix, flat, precision).reshape(b, s, d)


def hidden_states(cfg, p, tokens, precision="float32", chosen=None):
    """The final norm's output (b, S, d); ``chosen``, a list, collects
    each sparse layer's chosen experts (b * S, k)."""
    positions = jnp.arange(tokens.shape[1])
    x = p[EMBED][tokens]
    for prefix, conv, dense in _layers(cfg):
        layer = functools.partial(_layer, cfg=cfg, prefix=prefix, conv=conv,
                                  dense=dense, precision=precision,
                                  chosen=chosen)
        if chosen is None:
            # recompute inside each layer on the way back
            layer = jax.checkpoint(layer)
        x = layer(x, p, positions)
    return _rms(x, p["model.embedding_norm.gamma"], cfg["norm_eps"])


def per_sample_loss(cfg, p, batch, precision="float32"):
    (tokens,) = batch
    b, seq = tokens.shape
    y = hidden_states(cfg, p, tokens, precision)
    target = jnp.roll(tokens, -1, axis=1)
    blk = min(HEAD_BLOCK, seq)
    head = p[EMBED if cfg["tie_word_embeddings"] else HEAD]

    @jax.checkpoint
    def block(start):
        yb = jax.lax.dynamic_slice_in_dim(y, start, blk, axis=1)
        logits = _mm(yb, head, precision)                   # (b, blk, v)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tb = jax.lax.dynamic_slice_in_dim(target, start, blk, axis=1)
        return -jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]

    ce = jax.lax.map(block, jnp.arange(0, seq, blk))        # (blocks, b, blk)
    ce = ce.transpose(1, 0, 2).reshape(b, seq)
    # the last position has no next token
    return jnp.sum(ce[:, :-1], axis=1) / (seq - 1)


def held_rows(cfg, p, batch):
    """Rows the held experts of each sparse layer get from ``batch`` by
    the reference's own routing: int32 (sparse layers, held)."""
    chosen = []
    hidden_states(cfg, p, batch[0], chosen=chosen)
    first = cfg["ep_rank"] * cfg["num_experts"]
    held = first + jnp.arange(cfg["num_experts"])
    return jnp.stack([jnp.sum(e.reshape(-1)[None] == held[:, None], axis=1)
                      for e in chosen])


def forward_flops(cfg):
    import kernel_counts_hybrid

    return kernel_counts_hybrid.forward(cfg)
