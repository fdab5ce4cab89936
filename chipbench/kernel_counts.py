"""Operations and bytes of the SDAR cell's model and kernels, from shapes
alone (flops.py's rules: a multiply-add is 2, recomputation, padding and
dead tiles do not count).  tests/test_sdar_cell.py checks each against
a count by hand."""


def block_diffusion_pairs(seq, blen):
    """Query-key pairs the block-diffusion mask leaves, per head, over
    the 2 * seq positions [noisy ; clean]: a noisy query reads its own
    block (blen keys) and the clean blocks before it, a clean query the
    clean blocks up to its own — seq * blen + seq ** 2 in all."""
    blocks = seq // blen
    noisy_own = seq * blen
    noisy_clean = blen * blen * blocks * (blocks - 1) // 2
    clean_clean = blen * blen * blocks * (blocks + 1) // 2
    return noisy_own + noisy_clean + clean_clean


def attention_forward(cfg):
    """FLOPs of QK^T and PV of one layer, one sequence: 2 products of 2
    FLOPs a pair and a unit of head width, every query head."""
    return (4 * block_diffusion_pairs(cfg["seq"], cfg["block_length"])
            * cfg["head_dim"] * cfg["num_attention_heads"])


def expected_rows(cfg):
    """Rows a layer's held experts get from one sequence if the router
    spreads evenly: positions * experts per token * held / router width."""
    return (2 * cfg["seq"] * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / cfg["router_width"])


def experts_forward(cfg, rows):
    """FLOPs of the three grouped products on ``rows`` rows."""
    return rows * 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sdar_forward(cfg):
    """Forward FLOPs of one sequence: per layer the four projections on
    2 * seq positions, attention on the unmasked pairs, the router, the
    held experts on their expected rows; the head on the noisy half over
    the rows of the vocabulary held.  Norms, rotary, softmax, silu and
    the embedding look-up are left out."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pos = 2 * cfg["seq"]
    proj = 2 * pos * d * hd * (2 * h + 2 * kv)
    router = 2 * pos * d * cfg["router_width"]
    layer = (proj + attention_forward(cfg) + router
             + experts_forward(cfg, expected_rows(cfg)))
    head = 2 * cfg["seq"] * d * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer + head


def attention_kernels(cfg, batch):
    """(FLOPs, bytes) of the flash kernels of one training step, forward
    and backward, all layers: the backward's four products (dV, dP, dQ,
    dK) are twice the forward's two; q, o, dO, dQ move once per kernel
    that needs them (forward: q, o; backward: q, o, dO, dQ), k and v and
    their gradients likewise, 2 bytes an element."""
    layers = cfg["num_hidden_layers"]
    flops = 3 * attention_forward(cfg) * batch * layers
    pos, hd = 2 * cfg["seq"], cfg["head_dim"]
    q = batch * cfg["num_attention_heads"] * pos * hd
    kv = batch * cfg["num_key_value_heads"] * pos * hd
    return flops, 2 * (6 * q + 6 * kv) * layers


def expert_kernels(cfg, rows):
    """(FLOPs, bytes) of the grouped products of one training step on
    ``rows`` routed rows summed over the layers, forward and backward
    (gradients with respect to rows and to weights: twice the forward).
    Bytes: each held expert's three matrices read in the forward, read
    again for the rows' gradient and their gradient written, in every
    layer; per row the input, the two inner activations and the output,
    read or written 3 times over, 2 bytes an element."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3 * cfg["num_experts"] * d * f * cfg["num_hidden_layers"]
    per_row = 2 * d + 3 * f
    return (3 * experts_forward(cfg, rows),
            2 * (3 * weights + 3 * rows * per_row))


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def kernel_seconds(trace, scope_part=None, name_prefix=None):
    """Device seconds, over the whole trace, of the Pallas/Mosaic calls
    (``trace["kernels"]``) whose scope in the compile registry holds
    ``scope_part`` or whose instruction name starts with ``name_prefix``
    (XLA names the kernels it expands itself, and gives them no scope)."""
    import program_spans

    scopes = program_spans.op_scopes() or {}
    total = 0.0
    for name in trace.get("kernels") or ():
        if (scope_part and scope_part in scopes.get(name, "")) or (
                name_prefix and name.startswith(name_prefix)):
            total += trace["op_s"][name]
    return total
