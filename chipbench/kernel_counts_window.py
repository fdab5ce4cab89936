"""Operations and bytes of the window / global cell's model and flash
kernels — a stack of grouped-query attention layers of which most slide a
window and a few see every earlier key, each with a gated output, over
dense and sparse feed-forward layers beside a shared expert — from shapes
alone (flops.py's rules: a multiply-add is 2, recomputation, padding and
dead tiles do not count, so a roofline share reads the same work whatever
schedule runs it).  tests/test_trinity_cell.py checks each against a
count by hand."""
import kernel_counts
from kernel_counts_mla import causal_pairs


def applies(cfg):
    """Whether ``cfg`` describes such a stack: some of its ``layer_types``
    slide a window and it states one, the keys the counts here read.  The
    readers named ``window`` / ``global`` read nothing under any other
    configuration."""
    return bool(cfg.get("sliding_window")) and "sliding_attention" in (
        cfg.get("layer_types") or ())


def band_pairs(seq, window):
    """Query-key pairs a causal band of ``window`` keys leaves, per head:
    the first ``window`` queries see what the causal mask leaves them,
    W (W + 1) / 2, every later one exactly ``window`` keys."""
    if window >= seq:
        return causal_pairs(seq)
    return window * (window + 1) // 2 + (seq - window) * window


def layer_kinds(cfg):
    """(sliding layers, global layers, dense layers, sparse layers)."""
    sliding = sum(k == "sliding_attention" for k in cfg["layer_types"])
    layers = len(cfg["layer_types"])
    dense = min(cfg["num_dense_layers"], layers)
    return sliding, layers - sliding, dense, layers - dense


def attention_forward(cfg, sliding):
    """FLOPs of QK^T and PV of one layer, one sequence: 2 FLOPs a kept
    pair and a unit of width, keys and values both one head wide, every
    query head."""
    pairs = band_pairs(cfg["seq"], cfg["sliding_window"]) if sliding \
        else causal_pairs(cfg["seq"])
    return 2 * pairs * 2 * cfg["head_dim"] * cfg["num_attention_heads"]


def expected_rows(cfg):
    """Rows a layer's held experts get from one sequence if the router
    spreads evenly: positions * experts per token * held / router width."""
    return (cfg["seq"] * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_width"])


def forward(cfg):
    """Forward FLOPs of one sequence.  Every layer: its five projections
    (q, k, v, the output gate, o) and attention on the pairs its mask
    keeps.  A dense layer: its gated MLP.  A sparse layer: the router, the
    shared expert and the held experts on their expected rows.  The head
    on the S - 1 positions that have a next token, over the rows of the
    vocabulary held.  Norms, rotary, softmax, silu, the two sigmoids (the
    router's, the output gate's) and the embedding look-up are left out."""
    d, s, hd = cfg["hidden_size"], cfg["seq"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["moe_intermediate_size"]
    n_slide, n_global, n_dense, n_sparse = layer_kinds(cfg)
    proj = 2 * s * d * hd * (3 * h + 2 * kv)
    dense = 2 * s * 3 * d * cfg["intermediate_size"]
    sparse = (2 * s * d * cfg["router_width"]
              + 2 * s * 3 * d * f * cfg["num_shared_experts"]
              + kernel_counts.experts_forward(cfg, expected_rows(cfg)))
    head = 2 * (s - 1) * d * cfg["vocab_size"]
    return ((n_slide + n_global) * proj
            + n_slide * attention_forward(cfg, True)
            + n_global * attention_forward(cfg, False)
            + n_dense * dense + n_sparse * sparse + head)


def flash_roofline_pct(trace, run, sliding):
    """What the two readers `window_flash_roofline_pct.train` and
    `global_flash_roofline_pct.train` report: the least time the chip
    could take for the flash kernels of the traced steps over the sliding
    or over the global layers, over the device time of the Pallas calls
    under that kind's scope, in per cent.  None off a TPU, on a program
    without the scope and on a configuration of another kind."""
    import flops

    if (not run.get("traced_steps") or run["platform"] != "tpu"
            or not applies(run["cfg"])):
        return None
    scope = "/attention.window/" if sliding else "/attention.global/"
    seconds = kernel_counts.kernel_seconds(trace, scope_part=scope)
    if not seconds:
        return None
    least = kernel_counts.roofline_seconds(
        *attention_kernels(run["cfg"], run["batch"], sliding),
        flops.peaks(run["device_kind"]))
    return 100.0 * least * run["traced_steps"] / seconds


def attention_kernels(cfg, batch, sliding):
    """(FLOPs, bytes) of the flash kernels of one training step, forward
    and backward, over the sliding layers or over the global ones: the
    backward's four products (dV, dP, dQ, dK) are twice the forward's two.
    Bytes, 2 an element, as the accepted cells count them (q: read 3
    times, dQ and o written, dO read twice; k and v: read 3 times each,
    dK and dV written) — the whole tensors once a kernel, which is the
    least a band's kernels must move too: every key is inside some
    query's window."""
    n = layer_kinds(cfg)[0 if sliding else 1]
    flops = 3 * attention_forward(cfg, sliding) * batch * n
    q = batch * cfg["num_attention_heads"] * cfg["seq"] * cfg["head_dim"]
    kv = batch * cfg["num_key_value_heads"] * cfg["seq"] * cfg["head_dim"]
    return flops, 2 * (7 * q + 8 * kv) * n
