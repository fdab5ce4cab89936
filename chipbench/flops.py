"""Algorithmic FLOPs and bytes, from shapes alone.

What the mathematics of a model needs (a multiply-add is 2 FLOPs), not
what a compiled program happens to execute: recomputation, padding and
fusion artefacts do not count.  Every MFU and roofline share of the
benchmark divides by these and by ``peaks.json``.
"""
import json
import os

_RESNET_SPEC = {
    18: ("basic", (2, 2, 2, 2), (64, 64, 128, 256, 512)),
    34: ("basic", (3, 4, 6, 3), (64, 64, 128, 256, 512)),
    50: ("bottleneck", (3, 4, 6, 3), (64, 256, 512, 1024, 2048)),
}


def peaks(device_kind):
    """Published peaks of ``device_kind``; an unlisted device raises."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def conv2d(h, w, cin, cout, k, stride, pad):
    """(FLOPs, out_h, out_w) of one k×k convolution on an h×w×cin map."""
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w + 2 * pad - k) // stride + 1
    return 2 * k * k * cin * cout * oh * ow, oh, ow


def resnet_v1_forward(num_layers, image, classes, thumbnail=False):
    """Forward FLOPs of one image: convolutions and the classifier.
    BatchNorm, ReLU, pooling and the residual adds are left out (they are
    memory traffic, under 1% of the operations)."""
    kind, layers, channels = _RESNET_SPEC[num_layers]
    total, h, w = 0, image, image
    if thumbnail:
        f, h, w = conv2d(h, w, 3, channels[0], 3, 1, 1)
        total += f
    else:
        f, h, w = conv2d(h, w, 3, channels[0], 7, 2, 3)
        total += f
        h, w = (h + 2 - 3) // 2 + 1, (w + 2 - 3) // 2 + 1   # 3×3/2 max pool
    cin = channels[0]
    for i, n in enumerate(layers):
        cout = channels[i + 1]
        for j in range(n):
            stride = 2 if (j == 0 and i > 0) else 1
            if kind == "bottleneck":
                mid = cout // 4
                f1, h1, w1 = conv2d(h, w, cin, mid, 1, stride, 0)
                f2, h1, w1 = conv2d(h1, w1, mid, mid, 3, 1, 1)
                f3, h1, w1 = conv2d(h1, w1, mid, cout, 1, 1, 0)
                total += f1 + f2 + f3
            else:
                f1, h1, w1 = conv2d(h, w, cin, cout, 3, stride, 1)
                f2, h1, w1 = conv2d(h1, w1, cout, cout, 3, 1, 1)
                total += f1 + f2
            if j == 0 and cin != cout:
                total += conv2d(h, w, cin, cout, 1, stride, 0)[0]
            h, w, cin = h1, w1, cout
    return total + 2 * cin * classes


def bert_forward(num_layers, units, hidden_size, seq, head_outputs=2):
    """Forward FLOPs of one sequence of ``seq`` tokens: per layer the four
    attention projections, QK^T and PV, and the two feed-forward matmuls;
    plus the task head.  Embedding look-ups, LayerNorm, softmax and GELU
    are left out."""
    proj = 2 * seq * 4 * units * units
    attn = 2 * 2 * seq * seq * units
    ffn = 2 * seq * 2 * units * hidden_size
    return num_layers * (proj + attn + ffn) + 2 * seq * units * head_outputs


def forward_flops(cfg):
    """Forward FLOPs per sample of a configuration file's model: its
    plain reference (``reference/<builder>.py``, found by name like the
    rest) says which count above is its own, or brings one."""
    import importlib

    return importlib.import_module(
        "reference." + cfg["builder"]).forward_flops(cfg)


def train_flops(cfg):
    """Forward + backward per sample: the backward pass needs the
    gradient with respect to the input and to the weights of every
    matmul, twice the forward's operations."""
    return 3 * forward_flops(cfg)
