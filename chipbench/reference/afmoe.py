"""Plain reference: a decoder of model type ``afmoe``
(arcee-ai/Trinity-Mini) under the causal next-token objective, in
jax.numpy, float32, matmul precision ``highest``.  Imports nothing of the
program.

A dict of arrays keyed by the Gluon parameter names goes in, the loss of
each sequence comes out.  Batch element: tokens in [0, vocab_size)^S.
The equations are config.json's keys and, where it has none, the
published ``afmoe`` modelling code's (each of those is listed under
``assumed`` in the configuration file):

* h0 = Embed(tokens) * sqrt(hidden_size) (``mup_enabled``).
* Layer: a = x + N2(Attn(N1(x))); y = a + N4(FFN(N3(a))); N1..N4 =
  ``input_layernorm``, ``post_attention_layernorm``,
  ``pre_mlp_layernorm``, ``post_mlp_layernorm``; every norm an RMSNorm
  with a learned scale, ``rms_norm_eps``, float32; no biases anywhere.
* Attn: q, k, v, g = W_q z, W_k z, W_v z, W_g z as H / KV / KV / H heads
  of ``head_dim``; RMSNorm over each head's width of q and of k (one scale
  vector for all query heads, one for all key heads); on a layer whose
  ``layer_types`` entry is ``"sliding_attention"`` rotate-half rotary
  positions over the whole head, ``rope_theta``, positions 0 .. S - 1,
  and query i sees key j iff 0 <= i - j < ``sliding_window``; on a
  ``"full_attention"`` layer NO positions, and query i sees key j iff
  j <= i; query head h reads key-value head h // (H / KV); o_h =
  softmax(q_h k_h^T / sqrt(head_dim)) v_h over the keys seen; Attn =
  W_o ([o_1 .. o_H] * sigmoid(g)).
* FFN of the first ``num_dense_layers`` layers: one gated MLP
  W_down(silu(W_gate z) * W_up z), ``intermediate_size`` wide.
* FFN of every other layer: s = sigmoid(W_r z) in float32 over the
  router's full width; S = the ``num_experts_per_tok`` largest of s + b
  (b the router's bias, ``expert_bias`` in the published code); g_e =
  ``route_scale`` * s_e / (sum_S s + 1e-20) for e in S (``route_norm``)
  — the bias selects and never weighs, and no gradient reaches it; out =
  sum over e in S that are held of g_e * E_e(z) + E_shared(z), every E a
  gated MLP ``moe_intermediate_size`` wide (the shared one
  ``num_shared_experts`` times that).
* Head and loss: logits = W_head N(y) over the rows held, an untied head;
  loss of a sequence = (1 / (S - 1)) sum over i < S - 1 of
  CE(logits_i, tokens_{i+1}).

Departures, all of them the deployment's cut (the configuration file
states it): the chip holds ``num_experts`` of the router's
``router_width`` experts, from ``ep_rank * num_experts`` on, what the
absent experts would add is left out and the partial result goes on to
the next layer, while the shared expert is computed whole; the vocabulary
is the slice of ``vocab_size`` rows.  The bias is a seeded constant
(config.json gives no update speed) and there is no auxiliary loss
(``load_balance_coeff`` is the trainer's).  How it is computed, not what:
attention by blocks of queries against all keys, the head by blocks of
positions and each layer under ``jax.checkpoint`` so that 16,384
positions fit; every held expert is applied to every token and weighted
by its gate (zero where it was not chosen), so the reference has no
routing machinery, no schedule and no band arithmetic to share a fault
with: the band is two comparisons on a full row of scores.

``precision``: as in resnet_v1.py — the operands of every matrix product
whose weights the configuration keeps in ``dtype`` are rounded to that
type; the router, which the configuration keeps in float32, is not.
"""
import functools

import jax
import jax.numpy as jnp

from .precision import HI, _q

QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
HEAD_BLOCK = 512
NOT_TRAINED = ("running_load", "router_bias")


def _layers(cfg):
    """(prefix, layer slides its window, feed-forward is dense) of each
    layer."""
    return [(f"model.layers.{i}.", kind == "sliding_attention",
             i < cfg["num_dense_layers"])
            for i, kind in enumerate(cfg["layer_types"])]


def param_specs(cfg):
    """(name, shape, kind, arg, low) by Gluon name.  Matrices N(0, 0.02),
    rounded to the configuration's type except the router (float32 in the
    program too); the router's bias N(0, ``router_bias_std``), float32;
    norm scales U(0.9, 1.1)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held, v = cfg["moe_intermediate_size"], cfg["num_experts"], \
        cfg["vocab_size"]
    specs = []

    def mat(name, shape, low=True):
        specs.append((name, shape, "normal", 0.02, low))

    def scale(name, n):
        specs.append((name + ".gamma", (n,), "uniform", (0.9, 1.1), False))

    def mlp(prefix, width):
        mat(prefix + "gate_proj.weight", (width, d))
        mat(prefix + "up_proj.weight", (width, d))
        mat(prefix + "down_proj.weight", (d, width))

    mat("model.embed_tokens.weight", (v, d))
    for p, _sliding, dense in _layers(cfg):
        scale(p + "input_layernorm", d)
        mat(p + "self_attn.q_proj.weight", (h * hd, d))
        mat(p + "self_attn.k_proj.weight", (kv * hd, d))
        mat(p + "self_attn.v_proj.weight", (kv * hd, d))
        mat(p + "self_attn.o_proj.weight", (d, h * hd))
        mat(p + "self_attn.gate_proj.weight", (h * hd, d))
        scale(p + "self_attn.q_norm", hd)
        scale(p + "self_attn.k_norm", hd)
        scale(p + "post_attention_layernorm", d)
        scale(p + "pre_mlp_layernorm", d)
        scale(p + "post_mlp_layernorm", d)
        if dense:
            mlp(p + "mlp.", cfg["intermediate_size"])
            continue
        mat(p + "mlp.router", (cfg["router_width"], d), low=False)
        specs.append((p + "mlp.router_bias", (cfg["router_width"],),
                      "normal", cfg["router_bias_std"], False))
        mat(p + "mlp.gate_proj", (held, d, f))
        mat(p + "mlp.up_proj", (held, d, f))
        mat(p + "mlp.down_proj", (held, f, d))
        mlp(p + "mlp.shared.", cfg["num_shared_experts"] * f)
        # the layer's counters: state of the program, not of the model
        specs.append((p + "mlp.running_load", (3,), "const", 0.0, False))
    scale("model.norm", d)
    mat("lm_head.weight", (v, d))
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


def _attention(q, k, v, window, precision):
    """q: (b, heads, s, w); k, v: (b, kv heads, s, w).  One block of
    queries at a time against all keys, key j visible to query i iff
    j <= i and, with a ``window``, i - j < window; the query heads of a
    group read their key-value head."""
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
        gap = (start + jnp.arange(blk))[:, None] - k_pos[None, :]
        keep = gap >= 0
        if window is not None:
            keep = keep & (gap < window)
        att = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", _q(att, precision), vq,
                          precision=HI)

    out = jax.lax.map(block, jnp.arange(0, s, blk))  # (blocks, b, kv, g, blk, w)
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, s, w)


def attention(cfg, p, prefix, z, positions, sliding, precision):
    b, s, _ = z.shape
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]

    def heads(name, n, norm):
        t = _mm(z, p[prefix + f"self_attn.{name}_proj.weight"],
                precision).reshape(b, s, n, hd)
        if norm:
            t = _rms(t, p[prefix + f"self_attn.{name}_norm.gamma"],
                     cfg["rms_norm_eps"])
        t = t.transpose(0, 2, 1, 3)
        # only a layer that slides its window carries positions
        return _rope(t, positions, float(cfg["rope_theta"])) \
            if norm and sliding else t

    ctx = _attention(heads("q", h, True), heads("k", kv, True),
                     heads("v", kv, False),
                     cfg["sliding_window"] if sliding else None, precision)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    gate = _mm(z, p[prefix + "self_attn.gate_proj.weight"], precision)
    return _mm(ctx * jax.nn.sigmoid(gate),
               p[prefix + "self_attn.o_proj.weight"], precision)


def _mlp(p, prefix, z, precision):
    mid = jax.nn.silu(_mm(z, p[prefix + "gate_proj.weight"], precision)) \
        * _mm(z, p[prefix + "up_proj.weight"], precision)
    return _mm(mid, p[prefix + "down_proj.weight"], precision)


def route(cfg, p, prefix, z):
    """(gates (n, k), experts (n, k)) of the tokens z (n, d): float32."""
    logits = jnp.matmul(z, p[prefix + "mlp.router"].T, precision=HI)
    s = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p[prefix + "mlp.router_bias"])
    _, experts = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(s, experts, axis=-1)
    if cfg["route_norm"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * cfg["route_scale"], experts


def routed(cfg, p, prefix, z, precision):
    """The held experts' part of the layer for the tokens z (n, d): every
    held expert on every token, weighted by its gate (zero where it was
    not chosen), one block of tokens at a time."""
    gates, experts = route(cfg, p, prefix, z)
    first = cfg["ep_rank"] * cfg["num_experts"]
    held = first + jnp.arange(cfg["num_experts"])
    n, d = z.shape
    blk = min(TOKEN_BLOCK, n)
    wg, wu, wd = (_q(p[prefix + f"mlp.{m}_proj"], precision)
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


def _layer(x, p, positions, *, cfg, prefix, sliding, dense, precision):
    b, s, d = x.shape
    eps = cfg["rms_norm_eps"]

    def norm(name, t):
        return _rms(t, p[prefix + name + ".gamma"], eps)

    a = x + norm("post_attention_layernorm", attention(
        cfg, p, prefix, norm("input_layernorm", x), positions, sliding,
        precision))
    z = norm("pre_mlp_layernorm", a)
    if dense:
        ffn = _mlp(p, prefix + "mlp.", z, precision)
    else:
        ffn = (routed(cfg, p, prefix, z.reshape(b * s, d),
                      precision).reshape(b, s, d)
               + _mlp(p, prefix + "mlp.shared.", z, precision))
    return a + norm("post_mlp_layernorm", ffn)


def hidden_states(cfg, p, tokens, precision="float32"):
    """The final norm's output (b, S, d)."""
    positions = jnp.arange(tokens.shape[1])
    x = p["model.embed_tokens.weight"][tokens]
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"] ** 0.5
    for prefix, sliding, dense in _layers(cfg):
        # recompute inside each layer on the way back
        x = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, prefix=prefix, sliding=sliding, dense=dense,
            precision=precision))(x, p, positions)
    return _rms(x, p["model.norm.gamma"], cfg["rms_norm_eps"])


def per_sample_loss(cfg, p, batch, precision="float32"):
    (tokens,) = batch
    b, seq = tokens.shape
    y = hidden_states(cfg, p, tokens, precision)
    target = jnp.roll(tokens, -1, axis=1)
    blk = min(HEAD_BLOCK, seq)

    @jax.checkpoint
    def block(start):
        yb = jax.lax.dynamic_slice_in_dim(y, start, blk, axis=1)
        logits = _mm(yb, p["lm_head.weight"], precision)    # (b, blk, v)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tb = jax.lax.dynamic_slice_in_dim(target, start, blk, axis=1)
        return -jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]

    ce = jax.lax.map(block, jnp.arange(0, seq, blk))        # (blocks, b, blk)
    ce = ce.transpose(1, 0, 2).reshape(b, seq)
    # the last position has no next token
    return jnp.sum(ce[:, :-1], axis=1) / (seq - 1)


def forward_flops(cfg):
    import kernel_counts_window

    return kernel_counts_window.forward(cfg)
