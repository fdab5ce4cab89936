#!/usr/bin/env python
"""Operator micro-benchmark harness (reference: benchmark/opperf/ — per-op
forward/backward timing over the registered op corpus).

Runs a representative op sweep (elementwise, reduce, matmul/conv/norm NN
nucleus, random) at configurable shapes, timing jitted forward and
forward+backward, and emits one JSON line per op:
  {"op": ..., "shape": ..., "fwd_ms": ..., "fwd_bwd_ms": ...}

  python benchmark/opperf.py [--size 1024] [--iters 20] [--ops add,dot,...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _timed(fn, *args, iters=20, warmup=3):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e3


def build_suite(n):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import nn as _nn

    key = jax.random.PRNGKey(0)
    x2 = jax.random.normal(key, (n, n))
    v = jax.random.normal(key, (n * n,))
    img = jax.random.normal(key, (8, 32, max(n // 16, 8), max(n // 16, 8)))
    wconv = jax.random.normal(key, (32, 32, 3, 3)) * 0.1
    gamma = jnp.ones((32,))
    beta = jnp.zeros((32,))

    suite = {
        "add": (lambda a, b: a + b, (x2, x2)),
        "mul": (lambda a, b: a * b, (x2, x2)),
        "exp": (jnp.exp, (x2,)),
        "sum": (jnp.sum, (x2,)),
        "cumsum": (jnp.cumsum, (v,)),
        "sort": (jnp.sort, (v,)),
        "dot": (jnp.dot, (x2, x2)),
        "softmax": (lambda a: jax.nn.softmax(a, axis=-1), (x2,)),
        "layer_norm": (lambda a: _nn.layer_norm(
            a, jnp.ones((a.shape[-1],)), jnp.zeros((a.shape[-1],))),
            (x2,)),
        "conv2d": (lambda d, w: _nn.conv(d, w, None, pad=(1, 1)),
                   (img, wconv)),
        "batch_norm": (lambda d, g, b: _nn.batch_norm(
            d, g, b, jnp.zeros_like(g), jnp.ones_like(g),
            use_global_stats=True)[0], (img, gamma, beta)),
        "transpose": (lambda a: jnp.transpose(a), (x2,)),
        "take": (lambda a: jnp.take(a, jnp.arange(0, a.shape[0], 2),
                                    axis=0), (x2,)),
    }

    # round-2 hot ops: fused attention and MoE routing
    from mxnet_tpu.ops import pallas_attention as _pa
    from mxnet_tpu.parallel import moe as _moe

    s_att = min(max(n // 4, 64), 512)
    qkv = jax.random.normal(key, (2, 4, s_att, 64)) * 0.3
    suite["attention_reference"] = (
        lambda q: _pa.attention_reference(q, q, q), (qkv,))
    suite["flash_attention"] = (
        lambda q: _pa.flash_attention(
            q, q, q, interpret=jax.default_backend() != "tpu",
            block_q=64, block_k=64), (qkv,))
    mp = _moe.init_moe_params(key, 128, 256, 8)
    toks = jax.random.normal(key, (max(n // 2, 64), 128))
    suite["moe_ffn"] = (lambda t: _moe.moe_ffn(mp, t)[0], (toks,))
    return suite


def run(size=512, iters=20, ops=None):
    import jax
    import jax.numpy as jnp

    suite = build_suite(size)
    results = []
    for name, (fn, args) in suite.items():
        if ops and name not in ops:
            continue
        args = tuple(a for a in args if a is not None)
        jitted = jax.jit(fn)
        fwd = _timed(jitted, *args, iters=iters)

        if all(jnp.issubdtype(a.dtype, jnp.floating) for a in args):
            grad_fn = jax.jit(jax.grad(
                lambda *xs: jnp.sum(fn(*xs))))
            fwd_bwd = _timed(grad_fn, *args, iters=iters)
        else:
            fwd_bwd = None
        row = {"op": name, "shape": [list(a.shape) for a in args],
               "fwd_ms": round(fwd, 4),
               "fwd_bwd_ms": None if fwd_bwd is None else round(fwd_bwd, 4)}
        results.append(row)
        print(json.dumps(row))
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--size", type=int, default=512)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--ops", type=str, default=None,
                   help="comma-separated subset")
    args = p.parse_args(argv)
    import jax

    # one process, no probe child and no switch to another backend: the
    # rows are stamped with the device they ran on
    dev = jax.devices()[0]
    print(f"opperf on {dev.platform} ({dev.device_kind})", file=sys.stderr)
    run(args.size, args.iters, args.ops.split(",") if args.ops else None)


if __name__ == "__main__":
    main()
