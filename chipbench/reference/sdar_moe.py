"""Plain reference: the SDAR decoder (model type ``sdar_moe``,
JetLM/SDAR-30B-A3B-Chat) under the block-diffusion training objective, in
jax.numpy, float32, matmul precision ``highest``.  Imports nothing of the
program.

A dict of arrays keyed by the Gluon parameter names goes in, the loss of
each sequence comes out.  Batch element: clean tokens x0 in [0, MASK)^L,
u in U(0,1)^L, t in U(t_min,1)^(L/B); block of position i: b(i) = i // B;
masked_i = u_i < t_b(i); xt_i = MASK if masked_i else x0_i.  The network
sees the 2L positions [xt ; x0] with rotary positions [0..L-1 ; 0..L-1].

* Layer: a = RMSNorm(x); q = RMSNorm_head(W_q a), k = RMSNorm_head(W_k a)
  (over the head's width, learned scale), v = W_v a; q, k <- RoPE
  (rotate-half, theta from the configuration); the key-value head of
  query head h is h // (heads / key-value heads);
  h = x + W_o softmax(q k^T / sqrt(head width) + M) v;
  y = h + MoE(RMSNorm(h)).
* Mask M (allowed = 0, else -inf), query i, key j, "n" the noisy half and
  "c" the clean half: n->n iff b(i) = b(j); n->c iff b(j) < b(i); c->c iff
  b(j) <= b(i); c->n never (the vectorised training mask of block
  diffusion, Arriola et al., arXiv:2503.09573).
* MoE(z): p = softmax(W_r z) over the router's full width; S = the
  ``num_experts_per_tok`` largest; g_e = p_e / sum_S p
  (``norm_topk_prob``); out = sum over e in S that are held of
  g_e * W_d,e(silu(W_g,e z) * W_u,e z).
* Head and loss: logits = W_head RMSNorm(y) over the rows held, on the
  noisy half only; loss of a sequence = (1/L) sum over masked i of
  CE(logits_i, x0_i) / t_b(i).

Departures, all of them the deployment's cut (the configuration file
states it): the chip holds ``num_experts`` of the router's
``router_width`` experts, from ``ep_rank * num_experts`` on, and what the
absent experts would add is left out and the partial result goes on to
the next layer; the vocabulary is the slice of ``vocab_size`` rows, the
mask token its last row.  No auxiliary loss (the source gives no
coefficient).  How it is computed, not what: attention by blocks of
queries, the head by blocks of positions and each layer under
``jax.checkpoint`` so that 2 x 8192 positions fit; every held expert is
applied to every token and weighted by its gate (zero where it was not
chosen), so the reference has no routing machinery to share a fault with.

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
LOAD = "running_load"


def _layers(cfg):
    return [f"model.layers.{i}." for i in range(cfg["num_hidden_layers"])]


def param_specs(cfg):
    """(name, shape, kind, arg, low) by Gluon name.  Matrices N(0, 0.02),
    rounded to the configuration's type except the router (float32 in
    the program too); norm scales U(0.9, 1.1)."""
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], \
        cfg["moe_intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, v = cfg["num_experts"], cfg["vocab_size"]
    specs = []

    def mat(name, shape, low=True):
        specs.append((name, shape, "normal", 0.02, low))

    def scale(name, n):
        specs.append((name + ".gamma", (n,), "uniform", (0.9, 1.1), False))

    mat("model.embed_tokens.weight", (v, d))
    for p in _layers(cfg):
        scale(p + "input_layernorm", d)
        mat(p + "self_attn.q_proj.weight", (h * hd, d))
        mat(p + "self_attn.k_proj.weight", (kv * hd, d))
        mat(p + "self_attn.v_proj.weight", (kv * hd, d))
        mat(p + "self_attn.o_proj.weight", (d, h * hd))
        scale(p + "self_attn.q_norm", hd)
        scale(p + "self_attn.k_norm", hd)
        scale(p + "post_attention_layernorm", d)
        mat(p + "mlp.router", (cfg["router_width"], d), low=False)
        mat(p + "mlp.gate_proj", (held, d, f))
        mat(p + "mlp.up_proj", (held, d, f))
        mat(p + "mlp.down_proj", (held, f, d))
        # the layer's counters: state of the program, not of the model
        specs.append((p + "mlp." + LOAD, (2,), "const", 0.0, False))
    scale("model.norm", d)
    mat("lm_head.weight", (v, d))
    return tuple(specs)


def input_specs(cfg, batch):
    """Clean tokens below the mask token, u, and a noise level a block."""
    seq, blen = cfg["seq"], cfg["block_length"]
    return (((batch, seq), "randint", 0, cfg["mask_token_id"]),
            ((batch, seq), "uniform", 0.0, 1.0),
            ((batch, seq // blen), "uniform", cfg["t_min"], 1.0))


def trainable(name):
    return not name.endswith(LOAD)


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


def allowed(q_pos, k_pos, seq, blen):
    """The mask as a boolean (queries, keys) from position ids in
    [0, 2 * seq): True where the query may read the key."""
    qn, kn = q_pos[:, None] < seq, k_pos[None, :] < seq
    qb = (q_pos[:, None] % seq) // blen
    kb = (k_pos[None, :] % seq) // blen
    return ((qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


def _attention(q, k, v, seq, blen, precision):
    """q: (b, heads, 2 seq, w); k, v: (b, kv heads, 2 seq, w).  One block
    of queries at a time against all keys; the query heads of a group
    read their key-value head."""
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
        keep = allowed(start + jnp.arange(blk), k_pos, seq, blen)
        att = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", _q(att, precision), vq,
                          precision=HI)

    out = jax.lax.map(block, jnp.arange(0, s, blk))   # (blocks, b, kv, g, blk, w)
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, s, w)


def route(cfg, p, prefix, z):
    """(gates (n, k), experts (n, k)) of the tokens z (n, d): float32."""
    logits = jnp.matmul(z, p[prefix + "mlp.router"].T, precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return gates, experts


def _moe(cfg, p, prefix, z, precision):
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


def _layer(x, p, positions, *, cfg, prefix, precision, routed=None):
    b, s, d = x.shape
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps, seq = cfg["rms_norm_eps"], s // 2
    a = _rms(x, p[prefix + "input_layernorm.gamma"], eps)

    def heads(name, n, norm):
        t = _mm(a, p[prefix + f"self_attn.{name}_proj.weight"],
                precision).reshape(b, s, n, hd)
        if norm:
            t = _rms(t, p[prefix + f"self_attn.{name}_norm.gamma"], eps)
        t = t.transpose(0, 2, 1, 3)
        return _rope(t, positions, float(cfg["rope_theta"])) if norm else t

    ctx = _attention(heads("q", h, True), heads("k", kv, True),
                     heads("v", kv, False), seq, cfg["block_length"],
                     precision)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    x = x + _mm(ctx, p[prefix + "self_attn.o_proj.weight"], precision)
    z = _rms(x, p[prefix + "post_attention_layernorm.gamma"],
             eps).reshape(b * s, d)
    if routed is not None:
        routed.append(route(cfg, p, prefix, z)[1])
    return x + _moe(cfg, p, prefix, z, precision).reshape(b, s, d)


def _noise(cfg, x0, u, t):
    """(the 2L tokens the network sees, loss weight of each position)."""
    seq, blen = x0.shape[1], cfg["block_length"]
    t_pos = jnp.repeat(t, blen, axis=1)
    masked = u < t_pos
    tokens = jnp.concatenate(
        [jnp.where(masked, cfg["mask_token_id"], x0), x0], axis=1)
    return tokens, jnp.where(masked, 1.0 / t_pos, 0.0) / seq


def hidden_states(cfg, p, tokens, precision="float32", routed=None):
    """The final norm's output (b, 2L, d); ``routed``, a list, collects
    each layer's chosen experts (b * 2L, k)."""
    seq = tokens.shape[1] // 2
    positions = jnp.concatenate([jnp.arange(seq), jnp.arange(seq)])
    x = p["model.embed_tokens.weight"][tokens]
    for prefix in _layers(cfg):
        layer = functools.partial(_layer, cfg=cfg, prefix=prefix,
                                  precision=precision, routed=routed)
        if routed is None:
            # recompute inside each layer on the way back
            layer = jax.checkpoint(layer)
        x = layer(x, p, positions)
    return _rms(x, p["model.norm.gamma"], cfg["rms_norm_eps"])


def per_sample_loss(cfg, p, batch, precision="float32"):
    x0, u, t = batch
    tokens, weight = _noise(cfg, x0, u, t)
    b, seq = x0.shape
    y = hidden_states(cfg, p, tokens, precision)[:, :seq]
    blk = min(HEAD_BLOCK, seq)

    @jax.checkpoint
    def block(start):
        yb = jax.lax.dynamic_slice_in_dim(y, start, blk, axis=1)
        logits = _mm(yb, p["lm_head.weight"], precision)    # (b, blk, v)
        logp = jax.nn.log_softmax(logits, axis=-1)
        target = jax.lax.dynamic_slice_in_dim(x0, start, blk, axis=1)
        return -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]

    ce = jax.lax.map(block, jnp.arange(0, seq, blk))        # (blocks, b, blk)
    ce = ce.transpose(1, 0, 2).reshape(b, seq)
    return jnp.sum(weight * ce, axis=1)


def held_rows(cfg, p, batch):
    """Rows the held experts of each layer get from ``batch`` by the
    reference's own routing: int32 (layers, held)."""
    tokens, _ = _noise(cfg, *batch)
    routed = []
    hidden_states(cfg, p, tokens, routed=routed)
    first = cfg["ep_rank"] * cfg["num_experts"]
    held = first + jnp.arange(cfg["num_experts"])
    return jnp.stack([jnp.sum(e.reshape(-1)[None] == held[:, None], axis=1)
                      for e in routed])


def forward_flops(cfg):
    import kernel_counts

    return kernel_counts.sdar_forward(cfg)
