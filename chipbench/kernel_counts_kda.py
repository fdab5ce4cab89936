"""Operations and bytes of the delta / latent hybrid cell's model and of
its two kernel families, from shapes alone (flops.py's rules: a
multiply-add is 2, recomputation, padding and dead tiles do not count).
chipbench/tests/test_kimi_linear_cell.py checks each against a count by
hand.

**The scan's count is a floor**: the work no implementation of the
recurrence can avoid, whatever chunk it picks and whatever its backward
keeps, so that ``kda_scan_roofline_pct.train`` stays under 100 and does
not move when a later change picks another chunk.  A token of a head
reads the state with k (2 d_k d_v), writes the rank-one update (2 d_k
d_v) and reads the state with q (2 d_k d_v): 6 d_k d_v, the decay's own
d_k d_v multiplies left out with the rest of the elementwise work; the
backward is twice that.  What `npx.kda_scan` at a chunk of 64
really executes is several times more: per chunk of a head log2(64) = 6
masked products of (2 C x d_k) by (d_k x C) for the two E-weighted
matrices and one more for P's diagonal (26 C^2 d_k), four products with
the (d_k x d_v) state and P U (6 C d_k d_v + 2 C^2 d_v), the (C x C)
inverse by 12 float32 products (24 C^3) and T W (2 C^2 d_v) — 28.3 MFLOP a
chunk at d = 128, about 27 d_k d_v a token, in the forward; about two
and a half times that in the backward, which computes the forward's
parts again — and 6 * 2 * C * d_k exponentials a chunk beside the three
of Gamma.  The bytes: q, k, v, g (float32) and beta read
and o written once in the forward; the same operands and dO read, five
gradients written, in the backward; the states the backward keeps are
the implementation's and do not count.
"""
import kernel_counts
import kernel_counts_mla


def layer_kinds(cfg):
    """(delta layers, latent layers, dense layers, sparse layers) held."""
    lin = cfg["linear_attn_config"]
    held = cfg["layers_held"]
    n_kda = sum(n in lin["kda_layers"] for n in held)
    n_dense = sum(n <= cfg["first_k_dense_replace"] for n in held)
    return n_kda, len(held) - n_kda, n_dense, len(held) - n_dense


def applies(cfg):
    return "linear_attn_config" in cfg and "layers_held" in cfg


def scan_forward(cfg):
    """FLOPs of the recurrence of one layer, one sequence: 6 d_k d_v a
    token a head."""
    lin = cfg["linear_attn_config"]
    return 6 * lin["head_dim"] ** 2 * lin["num_heads"] * cfg["seq"]


def expected_rows(cfg):
    """Rows a layer's held experts get from one sequence if the router
    spreads evenly: positions * experts per token * held / router width."""
    return (cfg["seq"] * cfg["num_experts_per_token"] * cfg["num_experts"]
            / cfg["router_width"])


def mla_cfg(cfg):
    """The configuration as `kernel_counts_mla`'s functions read it: the
    latent layers alone."""
    return dict(cfg, num_hidden_layers=layer_kinds(cfg)[1])


def forward(cfg):
    """Forward FLOPs of one sequence.  A delta layer: its nine projection
    matrices (q, k, v, the decay's pair, beta, the gate's pair, o) and the
    recurrence.  A latent layer: its four projections and attention on
    the causal pairs.  A dense layer: its gated MLP.  A sparse layer: the
    router, the shared expert, the held experts on their expected rows.
    The head on the S - 1 positions that have a next token, over the rows
    of the vocabulary held.  Norms, the taps, silu, softplus, the sigmoids
    and the embedding look-up are left out."""
    d, s = cfg["hidden_size"], cfg["seq"]
    lin = cfg["linear_attn_config"]
    width, hd = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, f = cfg["v_head_dim"], cfg["moe_intermediate_size"]
    n_kda, n_mla, n_dense, n_sparse = layer_kinds(cfg)
    kda_proj = 2 * s * (4 * d * width + 2 * (d * hd + hd * width)
                        + d * lin["num_heads"])
    mla_proj = 2 * s * (d * h * (nope + rope) + d * (rank + rope)
                        + rank * h * (nope + vd) + h * vd * d)
    dense = 2 * s * 3 * d * cfg["intermediate_size"]
    sparse = (2 * s * d * cfg["router_width"]
              + 2 * s * 3 * d * f * cfg["num_shared_experts"]
              + expected_rows(cfg) * 2 * 3 * d * f)
    head = 2 * (s - 1) * d * cfg["vocab_size"]
    return (n_kda * (kda_proj + scan_forward(cfg))
            + n_mla * (mla_proj + kernel_counts_mla.attention_forward(cfg))
            + n_dense * dense + n_sparse * sparse + head)


def scan_kernels(cfg, batch):
    """(FLOPs, bytes) of the scan of one training step, forward and
    backward, all delta layers: the floor (the module's text).  Bytes:
    q, k, v at 2 an element and g at 4, beta at 4 a token a head, o
    written (2); then the same five and dO read, dq, dk, dv (2), dg (4)
    and dbeta (4) written."""
    lin = cfg["linear_attn_config"]
    n = layer_kinds(cfg)[0]
    tokens = batch * cfg["seq"] * lin["num_heads"]
    a_pass = tokens * (lin["head_dim"] * (3 * 2 + 4) + 4)
    out = tokens * lin["head_dim"] * 2
    return (3 * scan_forward(cfg) * batch * n,
            (a_pass + out + a_pass + out + a_pass) * n)


def _roofline_pct(trace, run, scope, counts):
    """The least time the chip could take for ``counts(cfg)`` = (FLOPs,
    bytes) a step over the device time of the Pallas calls under
    ``scope``, in per cent; None off a TPU, on a configuration of another
    kind, and on a program without such calls."""
    import flops

    if (not run.get("traced_steps") or run["platform"] != "tpu"
            or not applies(run["cfg"])):
        return None
    seconds = kernel_counts.kernel_seconds(trace, scope_part=scope)
    if not seconds:
        return None
    least = kernel_counts.roofline_seconds(
        *counts(run["cfg"]), flops.peaks(run["device_kind"]))
    return 100.0 * least * run["traced_steps"] / seconds


def scan_roofline_pct(trace, run):
    """The scans of the traced steps against their floor: the Pallas
    calls under ``kda.scan``."""
    return _roofline_pct(trace, run, "/kda.scan/",
                         lambda cfg: scan_kernels(cfg, run["batch"]))


def nope_flash_roofline_pct(trace, run):
    """The flash kernels under ``attention``, which in this stack lies
    inside ``mla`` alone (the latent layers, counted by
    `kernel_counts_mla.attention_kernels`)."""
    return _roofline_pct(
        trace, run, "/attention/",
        lambda cfg: kernel_counts_mla.attention_kernels(mla_cfg(cfg),
                                                        run["batch"]))
