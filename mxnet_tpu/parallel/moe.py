"""Expert parallelism: Mixture-of-Experts routing over an 'ep' mesh axis.

New capability beyond the reference (SURVEY §2.4: the reference has only
data parallelism). GShard-style top-k token routing: a router scores
tokens, dispatch/combine tensors route them to per-expert FFNs, and the
expert dimension is sharded over the mesh's 'ep' axis — XLA lowers the
dispatch einsums into all-to-alls over ICI.

The math follows the public GShard/Switch formulation (top-k gating with
capacity and auxiliary load-balancing loss); the implementation is dense
einsum routing, the layout XLA maps best onto the MXU.

`dropless_moe` is the other expert layer: top-k routing that drops
nothing, gated three-matrix experts, and a share of the experts — the
layer is told which experts it holds, routes over all of them and
computes its own experts' part of the result (docs/moe.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp

_jit_cache = {}

__all__ = ["top_k_routing", "moe_ffn", "moe_ffn_sharded", "init_moe_params",
           "route_top_k", "bias_moved_share", "dropless_moe", "buffer_rungs",
           "rung_index"]


def top_k_routing(router_logits, num_experts, capacity, top_k=2):
    """Compute dispatch/combine tensors from router logits.

    router_logits: (T, E) for T tokens. Returns
      dispatch (T, E, C) one-hot routing, combine (T, E, C) gate-weighted,
      aux_loss (scalar load-balancing loss, Switch-style).
    """
    T = router_logits.shape[0]
    probs = jax.nn.softmax(router_logits, axis=-1)           # (T, E)
    # top-k expert choices per token
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)      # (T, k)
    # position of each token within its expert's capacity buffer:
    # cumulative count of earlier tokens choosing the same expert
    onehot = jax.nn.one_hot(expert_idx, num_experts,
                            dtype=jnp.int32)                 # (T, k, E)
    # order: iterate k slots major so primary choices claim slots first
    flat = onehot.transpose(1, 0, 2).reshape(top_k * T, num_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat               # (k*T, E)
    pos = pos_flat.reshape(top_k, T, num_experts).transpose(1, 0, 2)
    slot = jnp.sum(pos * onehot, axis=-1)                    # (T, k)
    keep = slot < capacity
    gate_vals = gate_vals * keep
    # renormalize kept gates per token
    denom = jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    gate_vals = gate_vals / denom
    slot_oh = jax.nn.one_hot(jnp.where(keep, slot, capacity),
                             capacity + 1,
                             dtype=router_logits.dtype)[..., :capacity]
    exp_oh = jax.nn.one_hot(expert_idx, num_experts,
                            dtype=router_logits.dtype)       # (T, k, E)
    dispatch = jnp.einsum("tke,tkc->tec", exp_oh,
                          slot_oh * keep[..., None])
    combine = jnp.einsum("tke,tkc->tec", exp_oh,
                         slot_oh * gate_vals[..., None])
    # Switch aux loss: E * sum_e fraction_tokens_e * mean_prob_e
    primary = jax.nn.one_hot(expert_idx[:, 0], num_experts,
                             dtype=probs.dtype)
    frac = primary.mean(0)
    aux = num_experts * jnp.sum(frac * probs.mean(0))
    return dispatch, combine, aux


def init_moe_params(key, d_model, d_hidden, num_experts, dtype=jnp.float32):
    """Router + per-expert FFN weights (E stacked for ep sharding)."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / jnp.sqrt(d_model)
    scale_out = 1.0 / jnp.sqrt(d_hidden)
    return {
        "router": jax.random.normal(k1, (d_model, num_experts),
                                    dtype) * scale_in,
        "wi": jax.random.normal(k2, (num_experts, d_model, d_hidden),
                                dtype) * scale_in,
        "wo": jax.random.normal(k3, (num_experts, d_hidden, d_model),
                                dtype) * scale_out,
    }


def moe_ffn(params, x, capacity_factor=1.25, top_k=2):
    """MoE FFN over tokens x (T, D). Returns (out (T, D), aux_loss)."""
    T, D = x.shape
    E = params["router"].shape[1]
    capacity = max(1, int(capacity_factor * T * top_k / E))
    logits = x @ params["router"]
    dispatch, combine, aux = top_k_routing(logits, E, capacity, top_k)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)       # (E, C, D)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in,
                               params["wi"]))
    expert_out = jnp.einsum("ech,ehd->ecd", h, params["wo"])
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out, aux


def moe_ffn_sharded(params, x, mesh, axis="ep", capacity_factor=1.25,
                    top_k=2):
    """jit moe_ffn with the expert dimension sharded over `axis`; XLA
    inserts the token all-to-alls around the expert matmuls."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ep = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    params = {
        "router": jax.device_put(params["router"], repl),
        "wi": jax.device_put(params["wi"], ep),
        "wo": jax.device_put(params["wo"], ep),
    }
    x = jax.device_put(x, repl)

    key = (mesh, axis, capacity_factor, top_k)
    run = _jit_cache.get(key)
    if run is None:
        @jax.jit
        def run(p, xx):
            out, aux = moe_ffn(p, xx, capacity_factor, top_k)
            return out, aux

        _jit_cache[key] = run

    with mesh:
        return run(params, x)


# -- dropless routing over a held share of the experts ----------------------

def _scores(logits, scoring):
    """Each expert's float32 score from router ``logits`` (N, E):
    ``"softmax"`` over all E, or an independent ``"sigmoid"`` each."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    if scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    raise ValueError(f"scoring {scoring!r}: 'softmax' or 'sigmoid'")


def route_top_k(logits, top_k, normalize=True, *, scoring="softmax",
                bias=None, scale=1.0, normalize_eps=None):
    """(gates (N, k) float32, experts (N, k) int32) of router ``logits``
    (N, E): scores over all E in float32 (``scoring``: ``"softmax"``, or
    a ``"sigmoid"`` of each logit), the k largest, and — with
    ``normalize`` — the gates divided by their sum over those k plus
    ``normalize_eps`` (None: 1e-20 for a sigmoid, whose scores may all
    vanish, nothing for a softmax, whose k largest cannot), then times
    ``scale``.  A ``bias`` (E,) selects and never weighs: the k
    largest are taken of score + bias, the gates are the scores of the
    chosen without it, and no gradient reaches the bias."""
    scores = _scores(logits, scoring)
    if bias is None:
        gates, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    if normalize:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        if normalize_eps is None:
            normalize_eps = 1e-20 if scoring == "sigmoid" else 0.0
        gates = gates / (total + normalize_eps if normalize_eps else total)
    if scale != 1.0:
        gates = gates * scale
    return gates, experts.astype(jnp.int32)


def bias_moved_share(logits, experts, scoring):
    """The share of the assignments ``experts`` (N, k), chosen under a
    selection bias, that the k largest plain scores would not have made:
    0 says the bias did nothing."""
    _, plain = jax.lax.top_k(_scores(logits, scoring), experts.shape[1])
    kept = jnp.any(experts[:, :, None] == plain[:, None, :], axis=-1)
    return 1.0 - jnp.mean(kept.astype(jnp.float32))


def buffer_rungs(assignments, share):
    """The lengths a share's row buffer may take: 1.5 and 3 times
    ``assignments / share`` (what an even routing sends to one of ``share``
    chips) while that stays under ``assignments``, and always
    ``assignments`` itself — so nothing is ever dropped.  Few, because
    every rung is a copy of the layer's program, its grouped-product
    kernels included, that a process has to load before its first step."""
    even = -(-assignments // share)
    return tuple(even * m // 2 for m in (3, 6)
                 if even * m // 2 < assignments) + (assignments,)


def rung_index(rungs, rows):
    """Index of the first of ``rungs`` that holds ``rows`` rows.  ``rows``
    is a count traced on the device (`dropless_moe`) or one fetched to the
    host (`telemetry.instruments.flush_moe_load`)."""
    xp = jnp if isinstance(rows, jax.Array) else onp
    return xp.sum(xp.asarray(rungs) < rows)


def _take(x, idx):
    """Rows ``idx`` of ``x``; every index is in range (no pass to blank
    the rows of one that is not)."""
    return jnp.take(x, idx, axis=0, mode="clip")


def _at_rung(rungs, body, group_sizes, *operands):
    """``body(c, *operands)`` at the first rung ``c`` that holds the routed
    rows, chosen on the device.  One rung is no conditional."""
    branches = [functools.partial(body, c) for c in rungs]
    if len(branches) == 1:
        return branches[0](*operands)
    return jax.lax.switch(rung_index(rungs, jnp.sum(group_sizes)), branches,
                          *operands)


def _experts(xs, wgu, w_down, group_sizes):
    """down(silu(gate xs) * up xs) of each row by its group's expert."""
    f = w_down.shape[1]
    # gate and up side by side: the rows are read once, and their
    # gradient comes back as one product instead of a sum of two
    gu = jax.lax.ragged_dot(xs, wgu, group_sizes)
    return jax.lax.ragged_dot(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_down,
                              group_sizes)


def _rows(c, gates, order, group_sizes):
    """Of the buffer's ``c`` rows, the first of the sorted order: each
    row's assignment (n * k + j), its token, its gate, and whether
    anything was routed to it (a row past the routed ones holds some
    other chip's assignment; it belongs to no group)."""
    a = order[:c]
    routed = jnp.arange(c, dtype=jnp.int32) < jnp.sum(group_sizes)
    gate = jnp.where(routed, _take(gates.reshape(-1), a), 0.0)
    return a, a // gates.shape[1], gate, routed


def _sum_by_token(rows, token_of_row, n):
    """out[t] = the float32 sum of the ``rows`` whose token is t."""
    return jnp.zeros((n, rows.shape[1]), jnp.float32).at[token_of_row].add(
        rows)


def _forward_at(c, x, gates, order, group_sizes, wgu, w_down):
    _, tok, gate, routed = _rows(c, gates, order, group_sizes)
    with jax.named_scope("moe.dispatch"):
        xs = _take(x, tok)
    with jax.named_scope("moe.experts"):
        ys = _experts(xs, wgu, w_down, group_sizes)
    with jax.named_scope("moe.combine"):
        # what the grouped products left in a row of no group is not a
        # number anybody may read: select, never multiply by 0
        out = _sum_by_token(
            jnp.where(routed[:, None],
                      gate[:, None] * ys.astype(jnp.float32), 0.0),
            tok, x.shape[0])
    return out.astype(x.dtype)


def _backward_at(c, x, gates, order, group_sizes, wgu, w_down, dout):
    a, tok, gate, routed = _rows(c, gates, order, group_sizes)
    with jax.named_scope("moe.dispatch"):
        xs = _take(x, tok)
    with jax.named_scope("moe.experts"):
        ys, pull = jax.vjp(
            lambda xs, wgu, w_down: _experts(xs, wgu, w_down, group_sizes),
            xs, wgu, w_down)
    with jax.named_scope("moe.combine"):
        dy = _take(dout, tok).astype(jnp.float32)
        dys = (gate[:, None] * dy).astype(ys.dtype)
        dgate = jnp.where(
            routed, jnp.sum(ys.astype(jnp.float32) * dy, axis=-1), 0.0)
        dgates = jnp.zeros(gates.size, jnp.float32).at[a].set(
            dgate, unique_indices=True).reshape(gates.shape)
    with jax.named_scope("moe.experts"):
        dxs, dwgu, dw_down = pull(dys)
    with jax.named_scope("moe.dispatch"):
        dx = _sum_by_token(
            jnp.where(routed[:, None], dxs.astype(jnp.float32), 0.0),
            tok, x.shape[0])
    return dx.astype(x.dtype), dgates.astype(gates.dtype), dwgu, dw_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(rungs, x, gates, order, group_sizes, wgu, w_down):
    """out[t] = sum over the rows r of token t of gate[r] * experts(x[t]),
    in float32, at the first of ``rungs`` that holds the routed rows.
    ``order`` lists the N * k assignments by held expert, the ones routed
    elsewhere last.

    The conditional sits inside this function and inside its backward
    rule, never under JAX's transpose: a transposed `lax.switch` has every
    branch hand back every other branch's residuals as zeros, N * k rows
    each.  What is kept for the backward rule has one shape in every rung
    (the operands themselves), and the rule computes the rows again at its
    rung."""
    return _at_rung(rungs, _forward_at, group_sizes,
                    x, gates, order, group_sizes, wgu, w_down)


def _held_experts_fwd(rungs, *operands):
    return _held_experts(rungs, *operands), operands


def _held_experts_bwd(rungs, operands, dout):
    _, _, order, group_sizes, _, _ = operands
    dx, dgates, dwgu, dw_down = _at_rung(
        rungs, _backward_at, group_sizes, *operands, dout)
    return (dx, dgates, onp.zeros(order.shape, jax.dtypes.float0),
            onp.zeros(group_sizes.shape, jax.dtypes.float0), dwgu, dw_down)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def dropless_moe(x, router, w_gate, w_up, w_down, *, top_k, first_expert=0,
                 normalize=True, scoring="softmax", bias=None, scale=1.0,
                 normalize_eps=None):
    """One chip's share of a dropless mixture of gated experts.

    x: (N, D) tokens; router: (E, D), all E experts of the layer;
    w_gate, w_up: (held, D, F) and w_down: (held, F, D), the experts
    ``first_expert .. first_expert + held - 1`` that live here.  With
    p = softmax_E(router x) in float32, S the ``top_k`` largest and
    g_e = p_e / sum_S p (``normalize``):

        out = sum over e in S that are held of
              g_e * w_down[e](silu(w_gate[e] x) * w_up[e] x)

    ``scoring="sigmoid"`` scores each expert by itself, p_e =
    sigmoid(router x)_e; a ``bias`` (E,) float32 makes S the ``top_k``
    largest of p + bias while g stays p_e / sum_S p (`route_top_k`: the
    bias selects, never weighs, and has no gradient); ``scale``
    multiplies every g; ``normalize_eps`` is added to sum_S p
    (`route_top_k`'s rule when None).

    The sum over the held experts is this chip's part of the layer's
    result; what an expert held elsewhere adds is left out (the exchange
    that would add it belongs to the mesh, not to this function).  No
    token is dropped and there is no capacity: the N * k assignments are
    sorted by expert, the ones routed elsewhere last, and the layer runs
    on the sorted order's first C entries, C the first of
    ``buffer_rungs(N * k, E / held)`` that holds the rows routed here —
    chosen on the device, call by call; the last rung is N * k.  Gathers,
    grouped products (`jax.lax.ragged_dot`) and the sums back to the
    tokens all work on C rows.

    Returns (out (N, D), load float32): rows routed here, the largest
    held expert's rows over the mean of the held experts' rows and, with
    a ``bias``, `bias_moved_share` of this call — (2,), or (3,).
    """
    n = x.shape[0]
    held = w_gate.shape[0]
    with jax.named_scope("moe.router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            router.astype(jnp.float32).T,
                            precision=jax.lax.Precision.HIGHEST)
        gates, experts = route_top_k(logits, top_k, normalize,
                                     scoring=scoring, bias=bias, scale=scale,
                                     normalize_eps=normalize_eps)
    with jax.named_scope("moe.dispatch"):
        local = experts - first_expert
        key = jnp.where((local >= 0) & (local < held), local,
                        held).reshape(-1)                      # (N * k,)
        _, order = jax.lax.sort(
            (key, jnp.arange(n * top_k, dtype=jnp.int32)), num_keys=1)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=jnp.int32)[None],
            axis=0, dtype=jnp.int32)
        rows = jnp.sum(group_sizes)
        load = [rows, jnp.max(group_sizes) * held / jnp.maximum(rows, 1)]
        if bias is not None:
            load.append(bias_moved_share(logits, experts, scoring))
        load = jnp.stack(load).astype(jnp.float32)
    out = _held_experts(
        buffer_rungs(n * top_k, router.shape[0] // held), x, gates, order,
        group_sizes, jnp.concatenate([w_gate, w_up], axis=2), w_down)
    return out, load
