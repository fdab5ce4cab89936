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

import jax
import jax.numpy as jnp

_jit_cache = {}

__all__ = ["top_k_routing", "moe_ffn", "moe_ffn_sharded", "init_moe_params",
           "route_top_k", "dropless_moe"]


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

def route_top_k(logits, top_k, normalize=True):
    """(gates (N, k) float32, experts (N, k) int32) of router ``logits``
    (N, E): softmax over all E in float32, the k largest, and — with
    ``normalize`` — the gates divided by their sum over those k."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    if normalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


def _take(x, idx, fill=True):
    """Rows ``idx`` of ``x``.  An index past the end reads zeros; without
    ``fill`` it reads the last row, for a buffer whose rows past the
    routed ones nobody looks at — no pass over it to blank them."""
    if fill:
        return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)
    return jnp.take(x, idx, axis=0, mode="clip")


def _int_zero(a):
    import numpy as onp

    return onp.zeros(a.shape, jax.dtypes.float0)


@jax.custom_vjp
def _dispatch(x, token_of_row, slot):
    """xs[r] = x[token_of_row[r]]: the tokens in expert order.  ``slot``
    (N, k) is the row each assignment landed on (past the end: none), so
    the transpose is k gathers too, never a scatter.  A row past the
    routed ones holds some token's copy; it belongs to no group."""
    return _take(x, token_of_row, fill=False)


def _dispatch_fwd(x, token_of_row, slot):
    return _take(x, token_of_row, fill=False), (token_of_row, slot)


def _dispatch_bwd(res, dxs):
    token_of_row, slot = res
    dx = sum(_take(dxs, slot[:, j]).astype(jnp.float32)
             for j in range(slot.shape[1]))
    return dx.astype(dxs.dtype), _int_zero(token_of_row), _int_zero(slot)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, gates, slot, assignment_of_row):
    """out[n] = sum_j gates[n, j] * ys[slot[n, j]], summed in float32.
    ``assignment_of_row`` is ``slot``'s inverse (row -> n * k + j, past
    the end for a row nothing was routed to)."""
    out = sum(gates[:, j:j + 1] * _take(ys, slot[:, j]).astype(jnp.float32)
              for j in range(slot.shape[1]))
    return out.astype(ys.dtype)


def _combine_fwd(ys, gates, slot, assignment_of_row):
    return (_combine(ys, gates, slot, assignment_of_row),
            (ys, gates, slot, assignment_of_row))


def _combine_bwd(res, dout):
    ys, gates, slot, assignment_of_row = res
    k = slot.shape[1]
    gate_of_row = _take(gates.reshape(-1), assignment_of_row)
    # a row nothing was routed to has gate 0
    dys = (_take(dout, assignment_of_row // k, fill=False).astype(
        jnp.float32) * gate_of_row[:, None]).astype(ys.dtype)
    d32 = dout.astype(jnp.float32)
    dgates = jnp.stack(
        [jnp.sum(d32 * _take(ys, slot[:, j]).astype(jnp.float32), axis=-1)
         for j in range(k)], axis=1)
    return (dys, dgates.astype(gates.dtype), _int_zero(slot),
            _int_zero(assignment_of_row))


_combine.defvjp(_combine_fwd, _combine_bwd)


def dropless_moe(x, router, w_gate, w_up, w_down, *, top_k, first_expert=0,
                 normalize=True):
    """One chip's share of a dropless mixture of gated experts.

    x: (N, D) tokens; router: (E, D), all E experts of the layer;
    w_gate, w_up: (held, D, F) and w_down: (held, F, D), the experts
    ``first_expert .. first_expert + held - 1`` that live here.  With
    p = softmax_E(router x) in float32, S the ``top_k`` largest and
    g_e = p_e / sum_S p (``normalize``):

        out = sum over e in S that are held of
              g_e * w_down[e](silu(w_gate[e] x) * w_up[e] x)

    The sum over the held experts is this chip's part of the layer's
    result; what an expert held elsewhere adds is left out (the exchange
    that would add it belongs to the mesh, not to this function).  No
    token is dropped and there is no capacity: the N * k assignments are
    sorted by expert, the ones routed elsewhere last, and the grouped
    products (`jax.lax.ragged_dot`) work on the rows actually routed
    here — a row past their count belongs to no group and is neither
    computed nor read back.

    Returns (out (N, D), load (2,) float32): rows routed here, and the
    largest held expert's rows over the mean of the held experts' rows.
    """
    n, _d = x.shape
    held, _, f = w_gate.shape
    with jax.named_scope("moe.router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            router.astype(jnp.float32).T,
                            precision=jax.lax.Precision.HIGHEST)
        gates, experts = route_top_k(logits, top_k, normalize)
    with jax.named_scope("moe.dispatch"):
        local = experts - first_expert
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(-1)         # (N * k,)
        every = jnp.arange(n * top_k, dtype=jnp.int32)
        _, order = jax.lax.sort((key, every), num_keys=1)      # stable
        _, slot = jax.lax.sort((order, every), num_keys=1)     # its inverse
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=jnp.int32)[None],
            axis=0, dtype=jnp.int32)
        rows = jnp.sum(group_sizes)
        assignment_of_row = jnp.where(every < rows, order, n * top_k)
        slot = jnp.where(here, slot.reshape(n, top_k), n * top_k)
        xs = _dispatch(x, assignment_of_row // top_k, slot)
    with jax.named_scope("moe.experts"):
        # gate and up side by side: the rows are read once, and their
        # gradient comes back as one product instead of a sum of two
        gu = jax.lax.ragged_dot(
            xs, jnp.concatenate([w_gate, w_up], axis=2), group_sizes)
        ys = jax.lax.ragged_dot(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_down,
                                group_sizes)
    with jax.named_scope("moe.combine"):
        out = _combine(ys, gates, slot, assignment_of_row)
        load = jnp.stack([rows, jnp.max(group_sizes) * held
                          / jnp.maximum(rows, 1)]).astype(jnp.float32)
    return out, load
