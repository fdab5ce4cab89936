"""Operations and bytes of the looped cell's model and flash kernels, from
shapes alone (flops.py's rules: a multiply-add is 2, recomputation,
padding and dead tiles do not count).  tests/test_ouro_cell.py checks each
against a count by hand."""
from kernel_counts_mla import causal_pairs


def applications(cfg):
    """Layer applications of one step: every layer at every loop step."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def attention_forward(cfg):
    """FLOPs of QK^T and PV of one layer application, one sequence: 2
    FLOPs a causal pair and a unit of width, keys and values both
    ``head_dim`` wide, every query head."""
    return (2 * causal_pairs(cfg["seq"]) * 2 * cfg["head_dim"]
            * cfg["num_attention_heads"])


def forward(cfg):
    """Forward FLOPs of one sequence.  Every layer application: the four
    attention projections, attention on the unmasked pairs and the gated
    MLP's three products.  Every loop step: its exit's head on the S - 1
    positions that have a next token, over the whole vocabulary.  Norms,
    rotary, softmax, silu, the exit gate (2 x hidden a position) and the
    embedding look-up are left out."""
    d, hd, s = cfg["hidden_size"], cfg["head_dim"], cfg["seq"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * s * d * hd * (2 * h + 2 * kv)
    mlp = 2 * s * 3 * d * cfg["intermediate_size"]
    head = 2 * (s - 1) * d * cfg["vocab_size"]
    return (applications(cfg) * (proj + attention_forward(cfg) + mlp)
            + cfg["total_ut_steps"] * head)


def attention_kernels(cfg, batch):
    """(FLOPs, bytes) of the flash kernels of one training step, forward
    and backward, every layer application: the backward's four products
    (dV, dP, dQ, dK) are twice the forward's two.  Bytes, 2 an element:
    the forward reads q, k, v and writes o; the dQ kernel reads q, k, v,
    dO and writes dQ; the dK/dV kernel reads q, k, v, dO and writes dK and
    dV (o enters the backward through delta, a column)."""
    n = applications(cfg)
    flops = 3 * attention_forward(cfg) * batch * n
    q = batch * cfg["num_attention_heads"] * cfg["seq"] * cfg["head_dim"]
    kv = batch * cfg["num_key_value_heads"] * cfg["seq"] * cfg["head_dim"]
    # q: read 3 times, dQ and o written, dO read twice; k and v: read 3
    # times each, dK and dV written
    return flops, 2 * (7 * q + 8 * kv) * n
