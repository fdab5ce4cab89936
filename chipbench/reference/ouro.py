"""Plain reference: a looped decoder of model type ``ouro``
(ByteDance/Ouro-2.6B; arXiv:2510.25741) under its exit-weighted next-token
objective, in jax.numpy, float32, matmul precision ``highest``.  Imports
nothing of the program.

A dict of arrays keyed by the Gluon parameter names goes in, the loss of
each sequence comes out.  Batch element: tokens in [0, vocab_size)^S.
R = ``total_ut_steps``, N = ``num_hidden_layers``; no biases in attention
or MLP; every norm an RMSNorm with a learned scale, float32.

* Layer l, the same parameters at every loop step (sandwich norms):
  a = x + N2_l(Attn_l(N1_l(x))); y = a + N4_l(MLP_l(N3_l(a))).
  Attn: q, k, v = W_q z, W_k z, W_v z as H heads of ``head_dim``;
  rotate-half rotary positions (theta, positions 0..S-1) on q and k, no
  per-head norm; o_h = softmax_causal(q_h k_h^T / sqrt(head_dim)) v_h;
  W_o [o_1 .. o_H].  MLP: W_down(silu(W_gate z) * W_up z).
* Loop: h0 = Embed(tokens); for t = 1..R: ht = Norm(Layer_N(.. Layer_1(
  h(t-1)))) — the final norm is inside the loop, its output is exit t's
  hidden state AND the next loop step's input.
* Exit gate, float32: lambda_t = sigmoid(w_g . ht + b_g) per position;
  p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < R, p_R = prod_{j<R}
  (1 - lambda_j) (lambda_R is unused), from log_sigmoid(+-z).
* Loss: CE_t,i = CE(W_head ht_i, token_{i+1}) over the whole vocabulary;
  loss_i = sum_t p_t,i CE_t,i - beta H(p_i), H(p) = - sum_t p_t log p_t;
  a sequence's loss is the mean over its S - 1 positions that have a next
  token.

How it is computed, not what: Python loops over the R loop steps and the N
layers (no scan: the rolled loop is the program's), each layer application
under ``jax.checkpoint``, attention by blocks of queries and the head by
blocks of positions, so that 8,192 positions fit.

``precision``: as in resnet_v1.py — the operands of every matrix product
whose weights the configuration keeps in ``dtype`` are rounded to that
type; the exit gate, which the configuration keeps in float32, is not.
"""
import functools

import jax
import jax.numpy as jnp

from .precision import HI, _q

QUERY_BLOCK = 128
HEAD_BLOCK = 512
NOT_TRAINED = ("running_exit_mass",)
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


def param_specs(cfg):
    """(name, shape, kind, arg, low) by Gluon name.  Matrices N(0, 0.02),
    rounded to the configuration's type; norm scales U(0.9, 1.1); the exit
    gate's weight N(0, 0.02) and bias 0, float32."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    specs = []

    def mat(name, shape, low=True):
        specs.append((name, shape, "normal", 0.02, low))

    def scale(name):
        specs.append((name + ".gamma", (d,), "uniform", (0.9, 1.1), False))

    mat("model.embed_tokens.weight", (v, d))
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        for norm in NORMS:
            scale(p + norm)
        mat(p + "self_attn.q_proj.weight", (h * hd, d))
        mat(p + "self_attn.k_proj.weight", (kv * hd, d))
        mat(p + "self_attn.v_proj.weight", (kv * hd, d))
        mat(p + "self_attn.o_proj.weight", (d, h * hd))
        mat(p + "mlp.gate_proj.weight", (f, d))
        mat(p + "mlp.up_proj.weight", (f, d))
        mat(p + "mlp.down_proj.weight", (d, f))
    scale("model.norm")
    mat("lm_head.weight", (v, d))
    mat("exit_loss.exit_gate_weight", (d,), low=False)
    specs.append(("exit_loss.exit_gate_bias", (1,), "const", 0.0, False))
    # the objective's witness: state of the program, not of the model
    specs.append(("exit_loss.running_exit_mass", (cfg["total_ut_steps"],),
                  "const", 0.0, False))
    return tuple(specs)


def input_specs(cfg, batch):
    """Token ids drawn uniformly from the vocabulary."""
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
    """x: (b, s, heads, width), rotate-half."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def _attention(q, k, v, precision):
    """q: (b, heads, s, w); k, v: (b, kv heads, s, w).  One block of
    queries at a time against all keys, key j visible to query i iff
    j <= i."""
    b, h, s, w = q.shape
    group = h // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
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

    out = jax.lax.map(block, jnp.arange(0, s, blk))   # (blocks, b, h, blk, w)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, s, w)


def _attn(cfg, p, prefix, z, positions, precision):
    b, s, _ = z.shape
    hd, theta = cfg["head_dim"], float(cfg["rope_theta"])
    w = lambda name: p[prefix + f"self_attn.{name}_proj.weight"]  # noqa: E731
    q, k, v = (_mm(z, w(n), precision).reshape(b, s, -1, hd) for n in "qkv")
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    ctx = _attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                     precision)
    return _mm(ctx.transpose(0, 2, 1, 3).reshape(b, s, -1), w("o"),
               precision)


def _mlp(p, prefix, z, precision):
    mid = jax.nn.silu(_mm(z, p[prefix + "gate_proj.weight"], precision)) \
        * _mm(z, p[prefix + "up_proj.weight"], precision)
    return _mm(mid, p[prefix + "down_proj.weight"], precision)


def _layer(x, p, positions, *, cfg, prefix, precision):
    eps = cfg["rms_norm_eps"]
    norm = lambda name, t: _rms(t, p[prefix + name + ".gamma"], eps)  # noqa: E731
    a = x + norm("input_layernorm_2", _attn(
        cfg, p, prefix, norm("input_layernorm", x), positions, precision))
    return a + norm("post_attention_layernorm_2", _mlp(
        p, prefix + "mlp.", norm("post_attention_layernorm", a), precision))


def exit_states(cfg, p, tokens, precision="float32"):
    """[h1 .. hR], each (b, S, d): the final norm's output after each pass
    of the stack."""
    positions = jnp.arange(tokens.shape[1])
    x = p["model.embed_tokens.weight"][tokens]
    exits = []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            # recompute inside each layer application on the way back
            x = jax.checkpoint(functools.partial(
                _layer, cfg=cfg, prefix=f"model.layers.{i}.",
                precision=precision))(x, p, positions)
        x = _rms(x, p["model.norm.gamma"], cfg["rms_norm_eps"])
        exits.append(x)
    return exits


def _token_ce(p, y, target, precision):
    """CE(W_head y_i, target_i), (b, S), a block of positions at a time."""
    b, seq, _ = y.shape
    blk = min(HEAD_BLOCK, seq)

    @jax.checkpoint
    def block(start):
        yb = jax.lax.dynamic_slice_in_dim(y, start, blk, axis=1)
        logits = _mm(yb, p["lm_head.weight"], precision)    # (b, blk, v)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tb = jax.lax.dynamic_slice_in_dim(target, start, blk, axis=1)
        return -jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]

    ce = jax.lax.map(block, jnp.arange(0, seq, blk))        # (blocks, b, blk)
    return ce.transpose(1, 0, 2).reshape(b, seq)


def exit_distribution(cfg, p, exits):
    """[log p_1 .. log p_R], each (b, S): float32, the gate unrounded."""
    w, bias = p["exit_loss.exit_gate_weight"], p["exit_loss.exit_gate_bias"]
    log_stayed, out = 0.0, []
    for t, h in enumerate(exits):
        if t == len(exits) - 1:
            out.append(log_stayed + jnp.zeros(h.shape[:2], jnp.float32))
            break
        z = jnp.matmul(h, w, precision=HI) + bias
        out.append(log_stayed + jax.nn.log_sigmoid(z))
        log_stayed = log_stayed + jax.nn.log_sigmoid(-z)
    return out


def per_sample_loss(cfg, p, batch, precision="float32"):
    (tokens,) = batch
    seq = tokens.shape[1]
    exits = exit_states(cfg, p, tokens, precision)
    target = jnp.roll(tokens, -1, axis=1)
    total = 0.0
    for h, log_p in zip(exits, exit_distribution(cfg, p, exits)):
        prob = jnp.exp(log_p)
        total = total + prob * _token_ce(p, h, target, precision) \
            + cfg["entropy_beta"] * prob * log_p
    # the last position has no next token
    return jnp.sum(total[:, :-1], axis=1) / (seq - 1)


def forward_flops(cfg):
    import kernel_counts_looped

    return kernel_counts_looped.forward(cfg)
