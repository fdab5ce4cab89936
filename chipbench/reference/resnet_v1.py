"""Plain reference: ResNet v1 (He et al. 2015, arXiv:1512.03385) in
jax.numpy, float32, matmul precision ``highest``.

No kernels, no framework: a dict of arrays keyed by the Gluon parameter
names goes in, logits come out.  Departures from the paper, both
inherited from the MXNet model zoo this system reproduces: the stride of
a bottleneck's first block sits on its first 1x1 convolution (the
paper's original placement; "v1.5" moved it to the 3x3), and the loss is
the per-sample softmax cross-entropy, summed and rescaled by 1/batch in
the optimizer as ``Trainer.step(batch)`` does.

``precision`` is "float32" for the reference, or the name of a lower
floating type for the control: every operand of a convolution or matmul
is then rounded to that type with a per-tensor scale, and its cotangent
on the way back (float8_e5m2 for an e4m3 forward, as fp8 training does);
accumulation and everything elementwise stay float32 - the most
favourable way to use the type.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .precision import HI, _q

SPEC = {
    18: ("basic", (2, 2, 2, 2), (64, 64, 128, 256, 512)),
    34: ("basic", (3, 4, 6, 3), (64, 64, 128, 256, 512)),
    50: ("bottleneck", (3, 4, 6, 3), (64, 256, 512, 1024, 2048)),
}


# -- structure ---------------------------------------------------------------

def _blocks(cfg):
    """[(prefix, kind, cin, cout, stride, downsample)] in model order."""
    kind, layers, channels = SPEC[cfg["num_layers"]]
    first = 1 if cfg.get("thumbnail") else 4      # index of stage 1
    out, cin = [], channels[0]
    for i, n in enumerate(layers):
        cout = channels[i + 1]
        for j in range(n):
            stride = 2 if (j == 0 and i > 0) else 1
            out.append((f"features.{first + i}.{j}", kind, cin, cout, stride,
                        j == 0 and cin != cout))
            cin = cout
    return out, channels


def param_specs(cfg):
    """(name, shape, kind, arg, low) for every parameter, by Gluon name.
    Convolutions are He-normal over their fan-in; BatchNorm's scale,
    shift and running statistics are drawn away from their trivial
    values, so that leaving one out shows.  ``low`` marks the leaves the
    configuration holds in its low precision."""
    blocks, channels = _blocks(cfg)
    specs = []

    def conv(name, o, k, i):
        specs.append((name + ".weight", (o, k, k, i), "normal",
                      (2.0 / (k * k * i)) ** 0.5, True))

    def bn(name, c):
        specs.append((name + ".gamma", (c,), "uniform", (0.5, 1.0), False))
        specs.append((name + ".beta", (c,), "normal", 0.1, False))
        specs.append((name + ".running_mean", (c,), "normal", 0.1, False))
        specs.append((name + ".running_var", (c,), "uniform", (0.5, 1.5),
                      False))

    if cfg.get("thumbnail"):
        conv("features.0", channels[0], 3, 3)
    else:
        conv("features.0", channels[0], 7, 3)
        bn("features.1", channels[0])
    for prefix, kind, cin, cout, _stride, down in blocks:
        if kind == "bottleneck":
            mid = cout // 4
            conv(prefix + ".body.0", mid, 1, cin)
            bn(prefix + ".body.1", mid)
            conv(prefix + ".body.3", mid, 3, mid)
            bn(prefix + ".body.4", mid)
            conv(prefix + ".body.6", cout, 1, mid)
            bn(prefix + ".body.7", cout)
        else:
            conv(prefix + ".body.0", cout, 3, cin)
            bn(prefix + ".body.1", cout)
            conv(prefix + ".body.3", cout, 3, cout)
            bn(prefix + ".body.4", cout)
        if down:
            conv(prefix + ".downsample.0", cout, 1, cin)
            bn(prefix + ".downsample.1", cout)
    specs.append(("output.weight", (cfg["classes"], channels[-1]), "normal",
                  channels[-1] ** -0.5, True))
    specs.append(("output.bias", (cfg["classes"],), "normal", 0.01, True))
    return tuple(specs)


def input_specs(cfg, batch):
    """One training batch: images in [0, 1) and labels."""
    hw = cfg["image"]
    return (((batch, hw, hw, 3), "uniform", 0.0, 1.0),
            ((batch,), "randint", 0, cfg["classes"]))


def trainable(name):
    return not name.endswith(("running_mean", "running_var"))


# -- arithmetic --------------------------------------------------------------

def _conv(x, w, stride, pad, precision):
    return lax.conv_general_dilated(
        _q(x, precision), _q(w, precision), (stride, stride),
        ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "OHWI", "NHWC"), precision=HI)


def _bn(x, p, name, eps):
    """Training-mode BatchNorm: the batch's own statistics."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p[name + ".gamma"] \
        + p[name + ".beta"]


def _block(x, p, prefix, kind, stride, down, eps, precision):
    relu = jax.nn.relu
    b = prefix + ".body."
    if kind == "bottleneck":
        y = _conv(x, p[b + "0.weight"], stride, 0, precision)
        y = relu(_bn(y, p, b + "1", eps))
        y = _conv(y, p[b + "3.weight"], 1, 1, precision)
        y = relu(_bn(y, p, b + "4", eps))
        y = _conv(y, p[b + "6.weight"], 1, 0, precision)
        y = _bn(y, p, b + "7", eps)
    else:
        y = _conv(x, p[b + "0.weight"], stride, 1, precision)
        y = relu(_bn(y, p, b + "1", eps))
        y = _conv(y, p[b + "3.weight"], 1, 1, precision)
        y = _bn(y, p, b + "4", eps)
    if down:
        d = prefix + ".downsample."
        x = _conv(x, p[d + "0.weight"], stride, 0, precision)
        x = _bn(x, p, d + "1", eps)
    return relu(x + y)


def forward(cfg, p, x, precision="float32"):
    """Logits (batch, classes) in float32."""
    eps = cfg["bn_epsilon"]
    blocks, _ = _blocks(cfg)
    x = x.astype(jnp.float32)
    if cfg.get("thumbnail"):
        x = _conv(x, p["features.0.weight"], 1, 1, precision)
    else:
        x = _conv(x, p["features.0.weight"], 2, 3, precision)
        x = jax.nn.relu(_bn(x, p, "features.1", eps))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    for prefix, kind, _cin, _cout, stride, down in blocks:
        # recompute inside each block on the way back: what is kept is
        # one activation a block, so the timed batch fits in float32
        blk = jax.checkpoint(functools.partial(
            _block, prefix=prefix, kind=kind, stride=stride, down=down,
            eps=eps, precision=precision))
        x = blk(x, p)
    x = jnp.mean(x, (1, 2))
    return jnp.matmul(_q(x, precision), _q(p["output.weight"], precision).T,
                      precision=HI) + p["output.bias"]


def per_sample_loss(cfg, p, batch, precision="float32"):
    """Softmax cross-entropy of each row; training-mode BatchNorm."""
    x, y = batch
    logp = jax.nn.log_softmax(forward(cfg, p, x, precision), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None].astype(jnp.int32),
                                axis=-1)[:, 0]


def forward_flops(cfg):
    import flops

    return flops.resnet_v1_forward(cfg["num_layers"], cfg["image"],
                                   cfg["classes"],
                                   cfg.get("thumbnail", False))
