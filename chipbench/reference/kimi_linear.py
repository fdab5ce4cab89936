"""Plain reference: a decoder of model type ``kimi_linear``
(moonshotai/Kimi-Linear-48B-A3B-Instruct; arXiv:2510.26692) under the
causal next-token objective, in jax.numpy, float32, matmul precision
``highest``.  Imports nothing of the program.

A dict of arrays keyed by the Gluon parameter names goes in, the loss of
each sequence comes out.  Batch element: tokens in [0, vocab_size)^S.
Pre-norm residual layers, x <- x + Mix(RMSNorm(x)), x <- x +
FFN(RMSNorm(x)); RMSNorm in float32; a final norm; an untied head.
Layers are numbered from 1; ``layers_held`` says which published layers
the configuration holds, ``linear_attn_config``'s two lists which kind
each is.

* Kimi Delta Attention (a layer in ``kda_layers``), H heads, d wide, no
  biases:
      q = l2norm(silu(conv(W_q a)))  k = l2norm(silu(conv(W_k a)))
      v = silu(conv(W_v a))
  conv a depthwise causal convolution over the last
  ``short_conv_kernel_size`` positions (zeros before the sequence), l2norm
  x * rsqrt(sum x^2 + 1e-6) over a head;
      g = -exp(A_log[h]) * softplus(W_fb (W_fa a) + dt_bias)   per channel
      beta = sigmoid(W_b a)                                    one a head
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = d ** -0.5 * S_t^T q_t                 S_0 = 0, S (d x d) a head
      Mix = W_o [RMSNorm_head(o_t) * sigmoid(W_gb (W_ga a))]
  **as that recurrence, one position at a time** (`lax.scan`; blocks of
  positions are checkpoint segments so that 8,192 states need not be
  kept): no chunked form, so the reference shares no algebra with the
  program.
* Latent attention without positions (a layer in ``full_attn_layers``;
  ``mla_use_nope``): q = W_q a per head [q_nope ; q_pe];
  [c ; k_pe] = W_kv_a a with ONE k_pe for all heads; c <- RMSNorm(c);
  [k_nope ; v]_h = W_kv_b c; k_h = [k_nope_h ; k_pe], nothing rotated;
  o_h = softmax_causal(q_h k_h^T / sqrt(nope + pe)) v_h; W_o.
* FFN of a held layer whose published number is at most
  ``first_k_dense_replace``: a gated MLP, ``intermediate_size`` wide.
  Every other: s = sigmoid(W_r z) over the router's full width; the
  ``num_experts_per_token`` largest of s + b (one group); g_e =
  scaling * s_e / (sum of the chosen s + 1e-20) (``moe_renormalize``); out
  = sum over chosen e that are held of g_e E_e(z) + E_shared(z).
* Head and loss: logits = W_head RMSNorm(y) over the rows held; loss of a
  sequence = mean over i < S - 1 of CE(logits_i, tokens_{i+1}).  No
  multi-token prediction, no auxiliary loss.

Departures, all of them the deployment's cut (the configuration file
states it): the chip holds ``num_experts`` of the router's
``router_width`` experts, from ``ep_rank * num_experts`` on, what the
absent experts would add is left out and the partial result goes on;
the shared expert is computed whole; the vocabulary is the slice of
``vocab_size`` rows; the selection bias is a seeded constant.  How it is
computed, not what: attention by blocks of queries, the head by blocks of
positions, each half of a layer under ``jax.checkpoint``; every held expert is
applied to every token and weighted by its gate.

``precision``: as in resnet_v1.py — the operands of every matrix product
whose weights the configuration keeps in ``dtype`` are rounded to that
type, and q, k, v as they enter the recurrence or the scores; the router,
the taps, A_log, dt_bias, g, beta and the state are not.
"""
import functools

import jax
import jax.numpy as jnp

from .precision import HI, _q

QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
HEAD_BLOCK = 512
SCAN_BLOCK = 64
HEAD_GROUP = 8
L2_EPS = 1e-6
NOT_TRAINED = ("running_load", "router_bias")


def _layers(cfg):
    """(prefix, mixer, is dense) of each held layer."""
    lin = cfg["linear_attn_config"]
    out = []
    for i, n in enumerate(cfg["layers_held"]):
        kda, full = n in lin["kda_layers"], n in lin["full_attn_layers"]
        if kda == full:
            raise ValueError(f"layer {n}: in exactly one of the two lists")
        out.append((f"model.layers.{i}.", "kda" if kda else "mla",
                    n <= cfg["first_k_dense_replace"]))
    return out


def param_specs(cfg):
    """(name, shape, kind, arg, low) by Gluon name.  Matrices N(0, 0.02),
    rounded to the configuration's type except the router (float32 in the
    program too); norm scales U(0.9, 1.1); the taps U(-0.5, 0.5) (a
    ``Conv1d``'s own initialiser at 4 taps a channel), A_log U(0, log 16)
    and dt_bias U(softplus^-1(0.001), softplus^-1(0.1)), all three float32
    (``assumed`` in the configuration file says why)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    lin = cfg["linear_attn_config"]
    kh, kd, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    f, held, v = cfg["moe_intermediate_size"], cfg["num_experts"], \
        cfg["vocab_size"]
    specs = []

    def mat(name, shape, low=True):
        specs.append((name, shape, "normal", 0.02, low))

    def uniform(name, shape, lo, hi):
        specs.append((name, shape, "uniform", (lo, hi), False))

    def scale(name, n):
        uniform(name + ".gamma", (n,), 0.9, 1.1)

    def mlp(prefix, width):
        mat(prefix + "gate_proj.weight", (width, d))
        mat(prefix + "up_proj.weight", (width, d))
        mat(prefix + "down_proj.weight", (d, width))

    mat("model.embed_tokens.weight", (v, d))
    for p, mixer, dense in _layers(cfg):
        scale(p + "input_layernorm", d)
        a = p + "self_attn."
        if mixer == "kda":
            for t in "qkv":
                mat(a + f"{t}_proj.weight", (kh * kd, d))
            for t in "qkv":
                uniform(a + f"{t}_conv_taps", (kh * kd, taps), -0.5, 0.5)
            mat(a + "f_a_proj.weight", (kd, d))
            mat(a + "f_b_proj.weight", (kh * kd, kd))
            uniform(a + "dt_bias", (kh * kd,), *cfg["dt_bias_range"])
            uniform(a + "A_log", (kh,), *cfg["a_log_range"])
            mat(a + "b_proj.weight", (kh, d))
            mat(a + "g_a_proj.weight", (kd, d))
            mat(a + "g_b_proj.weight", (kh * kd, kd))
            scale(a + "o_norm", kd)
            mat(a + "o_proj.weight", (d, kh * kd))
        else:
            mat(a + "q_proj.weight", (h * (nope + rope), d))
            mat(a + "kv_a_proj.weight", (rank + rope, d))
            scale(a + "kv_a_norm", rank)
            mat(a + "kv_b_proj.weight", (h * (nope + vd), rank))
            mat(a + "o_proj.weight", (d, h * vd))
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


def _conv_silu(x, taps):
    """silu of the depthwise causal convolution: x (b, s, c), taps (c, L),
    tap L - 1 on the position itself, zeros before the sequence."""
    n, s = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[:, j] * padded[:, j:j + s]
                           for j in range(n)))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one position at a time.  q, k, g: (b, s, h, d); v:
    (b, s, h, dv); beta: (b, s, h).  Returns o (b, s, h, dv), without the
    scale.  How, not what: ``SCAN_BLOCK`` positions are one checkpoint
    segment, and the heads go ``HEAD_GROUP`` at a time (each head's
    recurrence is its own), so that the states kept for the way back are
    a group's."""
    b, s, h, d = q.shape
    group = HEAD_GROUP if h % HEAD_GROUP == 0 else h

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", kt, state, precision=HI)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - seen)[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state, precision=HI)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    blk = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    @jax.checkpoint
    def some_heads(xs):
        # (b, s, group, ..) -> (blocks, blk, b, group, ..)
        xs = tuple(jnp.moveaxis(t, 1, 0).reshape(
            (s // blk, blk) + t.shape[:1] + t.shape[2:]) for t in xs)
        first = jnp.zeros((b, group, d, v.shape[-1]), jnp.float32)
        _, out = jax.lax.scan(block, first, xs)
        return jnp.moveaxis(out.reshape((s,) + out.shape[2:]), 0, 1)

    def grouped(t):         # (b, s, h, ..) -> (groups, b, s, group, ..)
        return jnp.moveaxis(t.reshape(
            t.shape[:2] + (h // group, group) + t.shape[3:]), 2, 0)

    out = jax.lax.map(some_heads, tuple(grouped(t)
                                        for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 2).reshape((b, s, h, v.shape[-1]))


def _kda(cfg, p, prefix, a, precision):
    b, s, _ = a.shape
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    w = lambda name: p[prefix + f"self_attn.{name}.weight"]  # noqa: E731

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def stream(a_, w_, taps, normed):
        # a segment of its own: a stream's float32 stages (projection,
        # four shifted products, silu, norm) are 2 GB at the cell's size
        t = _conv_silu(_mm(a_, w_, precision), taps).reshape(b, s, h, d)
        return _l2norm(t) if normed else t

    q, k, v = (stream(a, w(f"{t}_proj"),
                      p[prefix + f"self_attn.{t}_conv_taps"], t != "v")
               for t in "qkv")
    @jax.checkpoint
    def decay(a_, w_a, w_b, bias, a_log):
        f = _mm(_mm(a_, w_a, precision), w_b, precision)
        return -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + bias).reshape(
            b, s, h, d)

    @jax.checkpoint
    def gated_out(o, a_, w_a, w_b, gamma, w_o):
        gate = _mm(_mm(a_, w_a, precision), w_b, precision).reshape(
            b, s, h, d)
        o = _rms(o, gamma, cfg["rms_norm_eps"]) * jax.nn.sigmoid(gate)
        return _mm(o.reshape(b, s, h * d), w_o, precision)

    g = decay(a, w("f_a_proj"), w("f_b_proj"),
              p[prefix + "self_attn.dt_bias"], p[prefix + "self_attn.A_log"])
    beta = jax.nn.sigmoid(_mm(a, w("b_proj"), precision))
    o = delta_rule(_q(q, precision), _q(k, precision), _q(v, precision),
                   g, beta) * d ** -0.5
    return gated_out(o, a, w("g_a_proj"), w("g_b_proj"),
                     p[prefix + "self_attn.o_norm.gamma"], w("o_proj"))


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


def _mla(cfg, p, prefix, a, precision):
    b, s, _ = a.shape
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    w = lambda name: p[prefix + f"self_attn.{name}.weight"]  # noqa: E731
    q = _mm(a, w("q_proj"), precision).reshape(b, s, h, -1)
    latent = _mm(a, w("kv_a_proj"), precision)
    c = _rms(latent[..., :rank], p[prefix + "self_attn.kv_a_norm.gamma"],
             cfg["rms_norm_eps"])
    kv = _mm(c, w("kv_b_proj"), precision).reshape(b, s, h, nope + vd)
    k_pe = latent[:, :, None, rank:]            # no positions: as it is
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, k_pe.shape[-1]))],
        -1)
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
    _, experts = jax.lax.top_k(s + bias, cfg["num_experts_per_token"])
    gates = jnp.take_along_axis(s, experts, axis=-1)
    if cfg["moe_renormalize"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * cfg["routed_scaling_factor"], experts


def _held(cfg):
    return cfg["ep_rank"] * cfg["num_experts"] + jnp.arange(
        cfg["num_experts"])


def _routed(cfg, p, prefix, z, precision):
    """The held experts' part of the layer for the tokens z (n, d): every
    held expert on every token, weighted by its gate (zero where it was
    not chosen), one block of tokens at a time."""
    gates, experts = route(cfg, p, prefix, z)
    held = _held(cfg)
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


def _mix_half(x, p, *, cfg, prefix, mixer, precision):
    mix = _kda if mixer == "kda" else _mla
    return x + mix(cfg, p, prefix,
                   _rms(x, p[prefix + "input_layernorm.gamma"],
                        cfg["rms_norm_eps"]), precision)


def _ffn_half(x, p, *, cfg, prefix, dense, precision, routed=None):
    b, s, d = x.shape
    z = _rms(x, p[prefix + "post_attention_layernorm.gamma"],
             cfg["rms_norm_eps"])
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
    x = p["model.embed_tokens.weight"][tokens]
    for prefix, mixer, dense in _layers(cfg):
        halves = (
            functools.partial(_mix_half, cfg=cfg, prefix=prefix, mixer=mixer,
                              precision=precision),
            functools.partial(_ffn_half, cfg=cfg, prefix=prefix, dense=dense,
                              precision=precision, routed=routed))
        for half in halves:
            if routed is None:
                # recompute inside each half of a layer on the way back: a
                # delta layer's float32 activations beside a 9,216-wide
                # MLP's do not fit the chip together
                half = jax.checkpoint(half)
            x = half(x, p)
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
    held = _held(cfg)
    return jnp.stack([jnp.sum(e.reshape(-1)[None] == held[:, None], axis=1)
                      for e in routed])


def forward_flops(cfg):
    import kernel_counts_kda

    return kernel_counts_kda.forward(cfg)
