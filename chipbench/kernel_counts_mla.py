"""Operations and bytes of the latent-attention cell's model and flash
kernels, from shapes alone (flops.py's rules: a multiply-add is 2,
recomputation, padding and dead tiles do not count).
tests/test_kanana2_cell.py checks each against a count by hand."""


def causal_pairs(seq):
    """Query-key pairs the causal mask leaves, per head: S (S + 1) / 2."""
    return seq * (seq + 1) // 2


def attention_forward(cfg):
    """FLOPs of QK^T and PV of one layer, one sequence: 2 FLOPs a pair and
    a unit of width, the keys' width (nope + rope) for QK^T and the
    values' for PV, every head."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (2 * causal_pairs(cfg["seq"]) * (qk + cfg["v_head_dim"])
            * cfg["num_attention_heads"])


def expected_rows(cfg):
    """Rows a layer's held experts get from one sequence if the router
    spreads evenly: positions * experts per token * held / router width."""
    return (cfg["seq"] * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / cfg["router_width"])


def forward(cfg):
    """Forward FLOPs of one sequence.  Every layer: the latent
    attention's four projections (q, the down projection to latent +
    rotary key, the up projection from the latent, the output) and
    attention on the unmasked pairs.  A dense layer: its gated MLP.  A
    sparse layer: the router, the shared expert, and the held experts on
    their expected rows.  The head on the S - 1 positions that have a next
    token, over the rows of the vocabulary held.  Norms, rotary, softmax,
    silu, the router's sigmoid and the embedding look-up are left out."""
    d, h, s = cfg["hidden_size"], cfg["num_attention_heads"], cfg["seq"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    f = cfg["moe_intermediate_size"]
    proj = 2 * s * (d * h * (nope + rope) + d * (rank + rope)
                    + rank * h * (nope + vd) + h * vd * d)
    dense = 2 * s * 3 * d * cfg["intermediate_size"]
    sparse = (2 * s * d * cfg["router_width"]
              + 2 * s * 3 * d * f * cfg["n_shared_experts"]
              + expected_rows(cfg) * 2 * 3 * d * f)
    n_dense = cfg["first_k_dense_replace"]
    layers = cfg["num_hidden_layers"]
    head = 2 * (s - 1) * d * cfg["vocab_size"]
    return (layers * (proj + attention_forward(cfg)) + n_dense * dense
            + (layers - n_dense) * sparse + head)


def attention_kernels(cfg, batch):
    """(FLOPs, bytes) of the flash kernels of one training step, forward
    and backward, all layers: the backward's four products (dV, dP, dQ,
    dK) are twice the forward's two.  Bytes, 2 an element, each tensor at
    its own width: the forward reads q, k, v and writes o; the dQ kernel
    reads q, k, v, dO and writes dQ; the dK/dV kernel reads q, k, v, dO
    and writes dK and dV (o enters the backward through delta, a column)."""
    layers = cfg["num_hidden_layers"]
    flops = 3 * attention_forward(cfg) * batch * layers
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rows = batch * cfg["num_attention_heads"] * cfg["seq"]
    wide, narrow = rows * qk, rows * cfg["v_head_dim"]
    # q and k: read 3 times each, dQ and dK written; v read 3 times, dV
    # written, o written, dO read twice
    return flops, 2 * (8 * wide + 7 * narrow) * layers
