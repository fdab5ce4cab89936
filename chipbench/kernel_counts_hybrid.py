"""Operations and bytes of the hybrid cell's model and kernels — a stack
of gated short convolutions and grouped-query attention over dense and
sparse feed-forward layers — from shapes alone (flops.py's rules: a
multiply-add is 2, recomputation, padding and dead tiles do not count).
tests/test_lfm2_cell.py checks each against a count by hand."""
import kernel_counts
from kernel_counts import experts_forward
from kernel_counts_mla import causal_pairs


def applies(cfg):
    """Whether ``cfg`` describes such a stack: it chooses each layer's
    operator (``layer_types``) and feed-forward (``num_dense_layers``),
    the keys the counts here read.  The readers named ``hybrid`` read
    nothing under any other configuration."""
    return "layer_types" in cfg and "num_dense_layers" in cfg


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kinds(cfg):
    """(convolution layers, attention layers, dense layers, sparse
    layers)."""
    conv = sum(kind == "conv" for kind in cfg["layer_types"])
    layers = len(cfg["layer_types"])
    dense = min(cfg["num_dense_layers"], layers)
    return conv, layers - conv, dense, layers - dense


def attention_forward(cfg):
    """FLOPs of QK^T and PV of one attention layer, one sequence: 2 FLOPs
    a causal pair and a unit of width, keys and values both one head
    wide, every query head."""
    return (2 * causal_pairs(cfg["seq"]) * 2 * head_dim(cfg)
            * cfg["num_attention_heads"])


def expected_rows(cfg):
    """Rows a layer's held experts get from one sequence if the router
    spreads evenly: positions * experts per token * held / router width."""
    return (cfg["seq"] * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_width"])


def forward(cfg):
    """Forward FLOPs of one sequence.  A convolution layer: its two
    projections (to three streams, and out).  An attention layer: its four
    projections and attention on the unmasked pairs.  A dense layer: its
    gated MLP.  A sparse layer: the router and the held experts on their
    expected rows.  The head on the S - 1 positions that have a next
    token, over the rows of the vocabulary held.  Norms, rotary, softmax,
    silu, the router's sigmoid, the embedding look-up and the
    convolution's own taps (2 L + 2 a position and channel: 0.03% of the
    layer's projections) are left out."""
    d, s = cfg["hidden_size"], cfg["seq"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    n_conv, n_attn, n_dense, n_sparse = layer_kinds(cfg)
    conv = 2 * s * d * 4 * d
    attn = 2 * s * d * hd * (2 * h + 2 * kv) + attention_forward(cfg)
    dense = 2 * s * 3 * d * cfg["intermediate_size"]
    sparse = (2 * s * d * cfg["router_width"]
              + experts_forward(cfg, expected_rows(cfg)))
    head = 2 * (s - 1) * d * cfg["vocab_size"]
    return (n_conv * conv + n_attn * attn + n_dense * dense
            + n_sparse * sparse + head)


def attention_kernels(cfg, batch):
    """(FLOPs, bytes) of the flash kernels of one training step, forward
    and backward, every attention layer: the backward's four products
    (dV, dP, dQ, dK) are twice the forward's two.  Bytes, 2 an element:
    the forward reads q, k, v and writes o; the backward reads q, k, v,
    dO and writes dQ, dK and dV, counted as the accepted cells count them
    (q: read 3 times, dQ and o written, dO read twice; k and v: read 3
    times each, dK and dV written)."""
    n = layer_kinds(cfg)[1]
    flops = 3 * attention_forward(cfg) * batch * n
    q = batch * cfg["num_attention_heads"] * cfg["seq"] * head_dim(cfg)
    kv = batch * cfg["num_key_value_heads"] * cfg["seq"] * head_dim(cfg)
    return flops, 2 * (7 * q + 8 * kv) * n


def expert_kernels(cfg, rows):
    """(FLOPs, bytes) of the grouped products of one training step on
    ``rows`` routed rows summed over the sparse layers, forward and
    backward: `kernel_counts.expert_kernels`' count, with the held
    experts' matrices in the sparse layers alone."""
    return kernel_counts.expert_kernels(
        dict(cfg, num_hidden_layers=layer_kinds(cfg)[3]), rows)


def mix_bytes(cfg, batch):
    """Bytes the short convolution's mix has to move in one training
    step, every convolution layer, 2 an element: the forward reads the
    three streams (T, 3D) and writes (T, D); the backward reads the three
    streams and the cotangent (T, D) and writes the streams' cotangent
    (T, 3D).  The least any implementation must move — a recomputed
    forward is not in it, nor are the taps (D x L numbers)."""
    t = batch * cfg["seq"]
    d = cfg["hidden_size"]
    return 2 * t * d * (3 + 1 + 3 + 1 + 3) * layer_kinds(cfg)[0]
