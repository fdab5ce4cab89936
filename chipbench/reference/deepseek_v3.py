"""Plain reference: a decoder of model type ``deepseek_v3``
(kakaocorp/kanana-2-30b-a3b-instruct-2601) under the causal next-token
objective, in jax.numpy, float32, matmul precision ``highest``.  Imports
nothing of the program.

A dict of arrays keyed by the Gluon parameter names goes in, the loss of
each sequence comes out.  Batch element: tokens in [0, vocab_size)^S.
Pre-norm residual layers, x <- x + Attn(RMSNorm(x)), x <- x +
FFN(RMSNorm(x)); RMSNorm in float32.

* Latent attention, H heads, no query compression (``q_lora_rank``
  null): q = W_q a, per head [q_nope (nope) ; q_rope (rope)];
  [c ; k_rope] = W_kv_a a with c ``kv_lora_rank`` wide and k_rope ONE for
  all heads; c <- RMSNorm(c); [k_nope (nope) ; v (v)]_h = W_kv_b c per
  head; rotary positions on q_rope and k_rope, the pair (2i, 2i + 1)
  turned by pos * theta ** (-2i / rope) (``rope_interleave``: the
  published code moves the pairs into the rotate-half layout first and
  leaves them there, and so does this; a score does not depend on a
  permutation common to q and k); k_h = [k_nope_h ; k_rope];
  o_h = softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h;
  Attn = W_o [o_1 .. o_H].  No biases.
* FFN of the first ``first_k_dense_replace`` layers: one gated MLP
  W_down(silu(W_gate z) * W_up z), ``intermediate_size`` wide.
* FFN of every other layer: s = sigmoid(W_r z) over the router's full
  width; S = the ``num_experts_per_tok`` largest of s + b (b the
  router's bias; one group, so no group step); g_e = scaling * s_e /
  (sum_S s + 1e-20) for e in S (``norm_topk_prob``) — the bias selects
  and never weighs, and no gradient reaches it; out = sum over e in S
  that are held of g_e * E_e(z) + E_shared(z), every E a gated MLP,
  ``moe_intermediate_size`` wide routed, ``n_shared_experts`` times that
  shared.
* Head and loss: logits = W_head RMSNorm(y) over the rows held; loss of
  a sequence = (1 / (S - 1)) sum over i < S - 1 of CE(logits_i,
  tokens_{i+1}).

Departures, all of them the deployment's cut (the configuration file
states it): the chip holds ``n_routed_experts`` of the router's
``router_width`` experts, from ``ep_rank * n_routed_experts`` on, what
the absent experts would add is left out and the partial result goes on
to the next layer, while the shared expert is computed whole; the
vocabulary is the slice of ``vocab_size`` rows.  The bias is a seeded
constant (config.json gives no update speed: the aux-loss-free update of
arXiv:2408.15664 and any sequence-wise auxiliary loss are left out).  How
it is computed, not what: attention by blocks of queries, the head by
blocks of positions and each layer under ``jax.checkpoint`` so that
2 x 8192 positions fit; every held expert is applied to every token and
weighted by its gate (zero where it was not chosen), so the reference
has no routing machinery to share a fault with.

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
    """(prefix, is dense) of each layer."""
    return [(f"model.layers.{i}.", i < cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])]


def param_specs(cfg):
    """(name, shape, kind, arg, low) by Gluon name.  Matrices N(0, 0.02),
    rounded to the configuration's type except the router (float32 in
    the program too); the router's bias N(0, ``router_bias_std``),
    float32; norm scales U(0.9, 1.1)."""
    d, h, rank = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, held, v = cfg["moe_intermediate_size"], cfg["n_routed_experts"], \
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
    for p, dense in _layers(cfg):
        scale(p + "input_layernorm", d)
        mat(p + "self_attn.q_proj.weight", (h * (nope + rope), d))
        mat(p + "self_attn.kv_a_proj.weight", (rank + rope, d))
        scale(p + "self_attn.kv_a_norm", rank)
        mat(p + "self_attn.kv_b_proj.weight", (h * (nope + vd), rank))
        mat(p + "self_attn.o_proj.weight", (d, h * vd))
        scale(p + "post_attention_layernorm", d)
        if dense:
            mlp(p + "mlp.", cfg["intermediate_size"])
            continue
        mat(p + "mlp.router", (cfg["router_width"], d), low=False)
        specs.append((p + "mlp.router_bias", (cfg["router_width"],),
                      "normal", cfg["router_bias_std"], False))
        mat(p + "mlp.gate_proj", (held, d, f))
        mat(p + "mlp.up_proj", (held, d, f))
        mat(p + "mlp.down_proj", (held, f, d))
        mlp(p + "mlp.shared.", cfg["n_shared_experts"] * f)
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
    """x: (b, s, heads, width) with neighbouring pairs: de-interleave,
    then rotate halves."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def _attention(q, k, v, precision):
    """q, k: (b, heads, s, w); v: (b, heads, s, wv).  One block of queries
    at a time against all keys, key j visible to query i iff j <= i."""
    b, h, s, w = q.shape
    k_pos = jnp.arange(s)
    blk = min(QUERY_BLOCK, s)
    kq, vq = _q(k, precision), _q(v, precision)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, axis=2)
        scores = jnp.einsum("bhqd,bhsd->bhqs", _q(qb, precision), kq,
                            precision=HI) / w ** 0.5
        keep = k_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        att = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bhsd->bhqd", _q(att, precision), vq,
                          precision=HI)

    out = jax.lax.map(block, jnp.arange(0, s, blk))   # (blocks, b, h, blk, wv)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, s, v.shape[-1])


def _mla(cfg, p, prefix, a, positions, precision):
    b, s, _ = a.shape
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    theta = float(cfg["rope_theta"])
    w = lambda name: p[prefix + f"self_attn.{name}.weight"]  # noqa: E731
    q = _mm(a, w("q_proj"), precision).reshape(b, s, h, -1)
    latent = _mm(a, w("kv_a_proj"), precision)
    c = _rms(latent[..., :rank], p[prefix + "self_attn.kv_a_norm.gamma"],
             cfg["rms_norm_eps"])
    kv = _mm(c, w("kv_b_proj"), precision).reshape(b, s, h, nope + vd)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], positions, theta)], -1)
    k_rope = _rope(latent[:, :, None, rank:], positions, theta)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, h,
                                                   k_rope.shape[-1]))], -1)
    ctx = _attention(*(t.transpose(0, 2, 1, 3)
                       for t in (q, k, kv[..., nope:])), precision)
    return _mm(ctx.transpose(0, 2, 1, 3).reshape(b, s, h * vd), w("o_proj"),
               precision)


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
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * cfg["routed_scaling_factor"], experts


def _routed(cfg, p, prefix, z, precision):
    """The held experts' part of the layer for the tokens z (n, d): every
    held expert on every token, weighted by its gate (zero where it was
    not chosen), one block of tokens at a time."""
    gates, experts = route(cfg, p, prefix, z)
    first = cfg["ep_rank"] * cfg["n_routed_experts"]
    held = first + jnp.arange(cfg["n_routed_experts"])
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


def _layer(x, p, positions, *, cfg, prefix, dense, precision, routed=None):
    b, s, d = x.shape
    eps = cfg["rms_norm_eps"]
    x = x + _mla(cfg, p, prefix,
                 _rms(x, p[prefix + "input_layernorm.gamma"], eps),
                 positions, precision)
    z = _rms(x, p[prefix + "post_attention_layernorm.gamma"], eps)
    if dense:
        return x + _mlp(p, prefix + "mlp.", z, precision)
    flat = z.reshape(b * s, d)
    if routed is not None:
        routed.append(route(cfg, p, prefix, flat)[1])
    return (x + _routed(cfg, p, prefix, flat, precision).reshape(b, s, d)
            + _mlp(p, prefix + "mlp.shared.", z, precision))


def hidden_states(cfg, p, tokens, precision="float32", routed=None):
    """The final norm's output (b, S, d); ``routed``, a list, collects
    each sparse layer's chosen experts (b * S, k)."""
    positions = jnp.arange(tokens.shape[1])
    x = p["model.embed_tokens.weight"][tokens]
    for prefix, dense in _layers(cfg):
        layer = functools.partial(_layer, cfg=cfg, prefix=prefix,
                                  dense=dense, precision=precision,
                                  routed=routed)
        if routed is None:
            # recompute inside each layer on the way back
            layer = jax.checkpoint(layer)
        x = layer(x, p, positions)
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


def held_rows(cfg, p, batch):
    """Rows the held experts of each sparse layer get from ``batch`` by
    the reference's own routing: int32 (sparse layers, held)."""
    routed = []
    hidden_states(cfg, p, batch[0], routed=routed)
    first = cfg["ep_rank"] * cfg["n_routed_experts"]
    held = first + jnp.arange(cfg["n_routed_experts"])
    return jnp.stack([jnp.sum(e.reshape(-1)[None] == held[:, None], axis=1)
                      for e in routed])


def forward_flops(cfg):
    import kernel_counts_mla

    return kernel_counts_mla.forward(cfg)
