#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the Gluon train / serve path
still starts on the chip.

One process drives the system's main path once on one TPU, through the
entry points a user calls, at the published widths of ResNet-50 and
BERT-base, with random weights made from ``--seed``:

  train_resnet50   README quick start: phased loop + gluon.TrainStep
  train_bert_base  bert_12_768_12 fine-tune step with the Pallas
                   flash-attention kernels, checked against the jnp
                   reference on the chip
  serve_resnet50   serving.InferenceEngine: warm the bucket ladder, then
                   answer mixed-size requests with zero retraces

``--chips 4`` runs ONLY the data-parallel whole step across four chips
and the one-device reference it is compared with.

It refuses to start unless ``jax.devices()[0].platform == "tpu"``, stops
at the first failure with a non-zero exit code, and prints as its last
line one JSON object: {"ok": true, "device": {...}}.  Times on earlier
lines are smoke output, not benchmark rows.
"""
import argparse
import functools
import json
import math
import os
import sys
import time

# Published widths (BASELINE.json configs).  The CPU rehearsal shrinks
# these from outside (it imports this module); the script itself has no
# size option.
CFG = {
    "resnet": {"batch": 128, "image": 224, "classes": 1000},
    "bert": {"batch": 8, "seq": 384, "vocab": 30522, "dropout": 0.1,
             "model": {}},        # model={} -> bert_12_768_12 as published
    "serve": {"max_batch": 8, "rows": (1, 3, 8, 2, 5, 1, 4, 8)},
    "flash": {"shape": (8, 12, 384, 64)},
    "dp": {"batch": 128, "image": 224, "classes": 1000},
}

_CACHE_EVENTS = {"hits": 0, "misses": 0}


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _count_cache_events():
    import jax

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            _CACHE_EVENTS["hits"] += 1
        elif event.endswith("/cache_misses"):
            _CACHE_EVENTS["misses"] += 1

    jax.monitoring.register_event_listener(on_event)


def _sync(nd):
    """Host-fetch: closes a timing on the device's last write."""
    return nd.asnumpy()


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _all_on(net, platform):
    for name, p in net.collect_params().items():
        for d in p.data()._data.devices():
            _check(d.platform == platform,
                   f"parameter {name} lives on {d}, not on a {platform}")


def _close(name, got, ref, rtol, atol):
    """max|got-ref| <= atol + rtol*max|ref| — one bound per tensor, on
    the scale of its largest entry.  Returns the error."""
    import numpy as onp

    got = onp.asarray(got, onp.float32)
    ref = onp.asarray(ref, onp.float32)
    err = float(onp.max(onp.abs(got - ref)))
    scale = float(onp.max(onp.abs(ref)))
    _check(math.isfinite(err) and err <= atol + rtol * scale,
           f"{name}: max abs error {err:.3e} over bound "
           f"{atol + rtol * scale:.3e} (max |ref| {scale:.3e})")
    return err


def _whole_steps(step, batch, n=3):
    """n calls of a gluon.TrainStep on one batch; (mean losses, seconds).
    Fails unless they ran as ONE traced whole-step program."""
    losses, times = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(*batch)
        losses.append(float(_sync(loss).mean()))
        times.append(time.perf_counter() - t0)
    _check(step.last_path == "whole_step",
           f"TrainStep ran {step.last_path}: {step.ineligible_reason()}")
    _check(step.jit_trace_count() == 1,
           f"whole step traced {step.jit_trace_count()} times")
    _check(all(math.isfinite(v) for v in losses),
           f"whole-step losses not finite: {losses}")
    return losses, times


# ---------------------------------------------------------------------------
# phase: train_resnet50
# ---------------------------------------------------------------------------

def _resnet(mx, ctx, classes, bf16=True):
    from mxnet_tpu import amp
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    net = resnet50_v1(classes=classes, layout="NHWC")
    net.initialize(ctx=ctx)
    if bf16:
        amp.convert_hybrid_block(net, target_dtype="bfloat16")
    net.hybridize()
    return net


def train_resnet50(mx, seed, platform):
    from mxnet_tpu import autograd, gluon

    c = CFG["resnet"]
    b, hw, classes = c["batch"], c["image"], c["classes"]
    ctx = mx.tpu(0)
    mx.seed(seed)
    net = _resnet(mx, ctx, classes)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="tpu_dist")
    with ctx:
        x = mx.np.random.uniform(size=(b, hw, hw, 3))
        y = mx.np.random.randint(0, classes, (b,))

    # the three-phase loop of the README quick start
    losses, times = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(b)
        losses.append(float(_sync(loss).mean()))
        times.append(time.perf_counter() - t0)
    _all_on(net, platform)
    _check(all(math.isfinite(v) for v in losses),
           f"phased losses not finite: {losses}")
    say("train_resnet50.phased", batch=b, image=hw, dtype="bfloat16",
        losses=losses, first_step_s_cold=round(times[0], 2),
        step_ms=round(times[1] * 1e3, 2))

    # the same step as ONE donated dispatch
    step = gluon.TrainStep(net, loss_fn, trainer)
    losses, times = _whole_steps(step, (x, y))
    _all_on(net, platform)
    say("train_resnet50.whole_step", losses=losses,
        last_path=step.last_path, jit_traces=step.jit_trace_count(),
        first_step_s_cold=round(times[0], 2),
        step_ms=round(min(times[1:]) * 1e3, 2))


# ---------------------------------------------------------------------------
# phase: train_bert_base
# ---------------------------------------------------------------------------

def _flash_parity():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_attention import (attention_reference,
                                                flash_attention)

    shape = CFG["flash"]["shape"]
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(11), 4)
    q = (jax.random.normal(kq, shape, jnp.float32) * 0.5).astype(jnp.bfloat16)
    k = (jax.random.normal(kk, shape, jnp.float32) * 0.5).astype(jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.float32).astype(jnp.bfloat16)
    seed = jnp.asarray([1234], jnp.int32)
    errs = {}
    for causal in (False, True):
        for p in (0.0, 0.1):
            kw_ = dict(causal=causal, dropout_p=p,
                       dropout_seed=seed if p else None)
            got = jax.jit(lambda a, b, c: flash_attention(a, b, c, **kw_))
            ref = jax.jit(lambda a, b, c: attention_reference(a, b, c, **kw_))
            _check("tpu_custom_call" in got.lower(q, k, v).compile().as_text(),
                   "flash_attention compiled without its Pallas kernel")
            errs[f"causal={int(causal)},dropout={p}"] = _close(
                f"flash_attention causal={causal} dropout={p}",
                got(q, k, v), ref(q, k, v), rtol=2e-2, atol=2e-2)
    # the two backward kernels (dQ, dK/dV) against autodiff of the
    # reference, with dropout on so the mask regenerates in both
    w = jax.random.normal(kw, shape, jnp.float32).astype(jnp.bfloat16)

    def grads(fn):
        def loss(a, b, c):
            out = fn(a, b, c, dropout_p=0.1, dropout_seed=seed)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    for name, g, r in zip(("dq", "dk", "dv"), grads(flash_attention),
                          grads(attention_reference)):
        errs[name] = _close(f"flash_attention {name}", g, r,
                            rtol=3e-2, atol=3e-2)
    return errs


def train_bert_base(mx, seed, platform):
    from mxnet_tpu import amp, diagnostics, gluon
    from mxnet_tpu.gluon.model_zoo.bert import BERTForQA, bert_12_768_12

    c = CFG["bert"]
    b, s = c["batch"], c["seq"]
    ctx = mx.tpu(0)
    mx.seed(seed)
    bert = bert_12_768_12(vocab_size=c["vocab"], dropout=c["dropout"],
                          **c["model"])
    layers = len(bert.encoder.layers)
    net = BERTForQA(bert, dropout=c["dropout"])
    net.initialize(ctx=ctx)
    amp.convert_hybrid_block(net, target_dtype="bfloat16")
    net.hybridize()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def span_loss(out, start, end):     # SQuAD span loss, per sample
        return ce(out[0], start) + ce(out[1], end)

    trainer = gluon.Trainer(
        net.collect_params(), "adam",
        {"learning_rate": 2e-5, "multi_precision": True},
        kvstore="tpu_dist")
    with ctx:
        tokens = mx.np.random.randint(0, c["vocab"], (b, s))
        segments = mx.np.zeros((b, s), dtype="int32")
        start = mx.np.random.randint(0, s, (b,))
        end = mx.np.random.randint(0, s, (b,))

    step = gluon.TrainStep(net, span_loss, trainer, n_data=2)
    losses, times = _whole_steps(step, (tokens, segments, start, end))
    _all_on(net, platform)
    # forward, dQ and dK/dV kernels of every layer are in the program
    entries = [e for (blk, _), e in diagnostics.compile_registry().items()
               if blk == "whole_step"]
    calls = max((e.get("tpu_custom_calls", 0) for e in entries), default=0)
    _check(calls >= 3 * layers,
           f"whole-step program holds {calls} Pallas calls, expected "
           f">= {3 * layers} (fwd, dQ, dK/dV per layer)")
    say("train_bert_base.whole_step", batch=b, seq=s, layers=layers,
        dtype="bfloat16", losses=losses, last_path=step.last_path,
        jit_traces=step.jit_trace_count(), pallas_calls=calls,
        first_step_s_cold=round(times[0], 2),
        step_ms=round(min(times[1:]) * 1e3, 2))
    say("train_bert_base.flash_parity", shape=list(CFG["flash"]["shape"]),
        max_abs_err=_flash_parity())


# ---------------------------------------------------------------------------
# phase: serve_resnet50
# ---------------------------------------------------------------------------

def serve_resnet50(mx, seed, platform):
    import numpy as onp

    from mxnet_tpu import serving
    from mxnet_tpu.telemetry import instruments as ti

    c, r = CFG["serve"], CFG["resnet"]
    hw = r["image"]
    ctx = mx.tpu(0)
    mx.seed(seed)
    net = _resnet(mx, ctx, r["classes"])
    eng = serving.InferenceEngine(net, name="resnet50",
                                  max_batch_size=c["max_batch"])
    t0 = time.perf_counter()
    warm = eng.warmup(mx.np.zeros((1, hw, hw, 3)))
    warm_s = time.perf_counter() - t0
    _all_on(net, platform)

    def traces():
        return sum(child.value for _, child in ti.jit_trace_total.series())

    # one full bucket through the block directly is the reference; the
    # requests are slices of it, so net(x) itself compiles nothing new
    rs = onp.random.RandomState(seed)
    full = rs.rand(c["max_batch"], hw, hw, 3).astype("float32")
    want = _sync(net(mx.np.array(full, ctx=ctx))).astype("float32")
    traces0 = traces()
    eng.start()
    try:
        reqs, at = [], 0
        for rows in c["rows"]:
            idx = [(at + i) % c["max_batch"] for i in range(rows)]
            at += rows
            reqs.append((idx, eng.submit(full[idx])))
        errs = []
        for idx, req in reqs:
            out = req.result(timeout=120)
            _check(req.done and req.outcome == "ok",
                   f"request settled as {req.outcome}")
            errs.append(_close(f"served rows {idx}", _sync(out), want[idx],
                               rtol=2e-2, atol=1e-3))
    finally:
        eng.stop()
    _check(traces() == traces0,
           f"jit_trace_total moved {traces0} -> {traces()} while serving")
    _check(eng.recompiles_since_warmup() == 0,
           f"{eng.recompiles_since_warmup()} recompiles after warmup")
    say("serve_resnet50", buckets=warm["buckets"],
        warmup_s_cold=round(warm_s, 2), requests=len(reqs),
        rows=list(c["rows"]),
        retraces_after_warmup=eng.recompiles_since_warmup(),
        max_abs_err_vs_direct=max(errs), stats=eng.stats())


# ---------------------------------------------------------------------------
# --chips 4: the data-parallel whole step and its one-device reference
# ---------------------------------------------------------------------------

def train_dp4(mx, seed, platform, chips):
    import jax
    import numpy as onp

    from mxnet_tpu import autograd, diagnostics, gluon

    # float32 end to end, true-f32 matmuls included: the comparison below
    # is held to a float32 tolerance, which bf16 rounding (of parameters,
    # or inside the MXU's default single-pass f32 matmul) would exceed
    jax.config.update("jax_default_matmul_precision", "highest")
    c = CFG["dp"]
    b, hw, classes = c["batch"], c["image"], c["classes"]
    per = b // chips
    rs = onp.random.RandomState(seed)
    xs = [rs.rand(b, hw, hw, 3).astype("float32") for _ in range(3)]
    ys = [rs.randint(0, classes, (b,)).astype("int32") for _ in range(3)]
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = {"learning_rate": 0.05, "momentum": 0.9}
    ctx = mx.tpu(0)

    def build(like=None, **trainer_kw):
        mx.seed(seed)
        net = _resnet(mx, ctx, classes, bf16=False)
        net(mx.np.zeros((2, hw, hw, 3), ctx=ctx))   # finish deferred init
        if like is not None:        # same start, whatever the RNG order
            for n, p in net.collect_params().items():
                p.set_data(like[n])
        trainer = gluon.Trainer(net.collect_params(), "sgd", dict(opt),
                                kvstore="tpu_dist", **trainer_kw)
        return net, trainer

    def snapshot(net):
        return {n: _sync(p.data())
                for n, p in sorted(net.collect_params().items())}

    # A: one program across the chips — batch sharded over dp, gradients
    # all-reduced in-program
    net, trainer = build(sharding_plan=f"dp={chips}")
    start = snapshot(net)
    step = gluon.TrainStep(net, loss_fn, trainer)
    losses_dp, times = [], []
    for k in range(3):
        t0 = time.perf_counter()
        loss = step(mx.np.array(xs[k], ctx=ctx), mx.np.array(ys[k], ctx=ctx))
        losses_dp.append(_sync(loss))
        times.append(time.perf_counter() - t0)
    _check(step.last_path == "whole_step",
           f"TrainStep ran {step.last_path}: {step.ineligible_reason()}")
    _check(step.jit_trace_count() == 1,
           f"whole step traced {step.jit_trace_count()} times")
    devs = {d for s in loss._data.addressable_shards for d in [s.device]}
    _check(len(devs) == chips and all(d.platform == platform for d in devs),
           f"loss shards live on {sorted(map(str, devs))}, expected "
           f"{chips} distinct {platform} devices")
    for name, p in net.collect_params().items():
        pd = {s.device for s in p.data()._data.addressable_shards}
        _check(len(pd) == chips,
               f"parameter {name} is on {len(pd)} device(s): everything "
               f"was placed on a leading subset, not on the {chips}-chip "
               f"mesh")
    entries = [e for (blk, _), e in diagnostics.compile_registry().items()
               if blk == "whole_step"]
    _check(entries and all(e.get("all_reduces", 0) > 0 for e in entries),
           "the compiled whole step holds no all-reduce")
    params_dp = snapshot(net)

    # B: the same math on ONE device.  In the data-parallel program every
    # shard normalizes with its OWN batch statistics, starts from the
    # step's running statistics and the shards' new ones are averaged;
    # the reference does exactly that, one shard after another through
    # the phased loop, accumulating gradients (grad_req='add') and taking
    # one update per global batch.
    net1, trainer1 = build(like=start)
    aux = []
    for p in net1.collect_params().values():
        if p.grad_req == "null":
            aux.append(p)
        else:
            p.grad_req = "add"
    losses_1 = []
    for k in range(3):
        parts = []
        aux_start = [_sync(p.data()) for p in aux]
        aux_sum = [0.0] * len(aux)
        for i in range(chips):
            sl = slice(i * per, (i + 1) * per)
            for p, v in zip(aux, aux_start):
                p.set_data(v)
            with autograd.record():
                loss = loss_fn(net1(mx.np.array(xs[k][sl], ctx=ctx)),
                               mx.np.array(ys[k][sl], ctx=ctx))
            loss.backward()
            parts.append(_sync(loss))
            aux_sum = [a + _sync(p.data()) for a, p in zip(aux_sum, aux)]
        for p, a in zip(aux, aux_sum):
            p.set_data(a / chips)
        trainer1.step(b)
        net1.zero_grad()
        losses_1.append(onp.concatenate(parts))
    params_1 = snapshot(net1)

    # tolerance of tests/test_train_step.py's CPU-mesh twin
    loss_err = max(_close(f"dp loss step {k}", a, r, rtol=1e-5, atol=1e-6)
                   for k, (a, r) in enumerate(zip(losses_dp, losses_1)))
    param_err = max(_close(f"dp parameter {n}", params_dp[n], params_1[n],
                           rtol=1e-5, atol=1e-6) for n in params_1)
    say("train_dp4", chips=chips, global_batch=b, dtype="float32",
        devices=sorted(map(str, devs)), last_path=step.last_path,
        jit_traces=step.jit_trace_count(),
        loss_mean=[float(v.mean()) for v in losses_dp],
        max_abs_err_loss=loss_err, max_abs_err_params=param_err,
        first_step_s_cold=round(times[0], 2),
        step_ms=round(min(times[1:]) * 1e3, 2))


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel step across four "
                         "chips and its one-device reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax

    # the package first: importing it places the compile cache (and fails
    # here, before anything runs, where the repo is not around the script)
    import mxnet_tpu as mx
    from mxnet_tpu import _native
    from mxnet_tpu.telemetry.instruments import device_peaks

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              f"device(s); nothing was run", file=sys.stderr)
        return 2
    _count_cache_events()

    say("start", platform=dev.platform, device_kind=dev.device_kind,
        device_count=len(devs), jax=jax.__version__,
        native_runtime_loaded=_native.available(),
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        compile_cache_placed_by=(
            "JAX_COMPILATION_CACHE_DIR"
            if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "mxnet_tpu"),
        peaks=device_peaks(dev.device_kind))
    _check(_native.available(),
           "native runtime did not load (native/*.cc failed to build?)")

    if args.chips == 4:
        phases = {"train_dp4": functools.partial(train_dp4, chips=4)}
    else:
        phases = {f.__name__: f for f in (train_resnet50, train_bert_base,
                                          serve_resnet50)}
    for name, phase in phases.items():
        t0 = time.perf_counter()
        hits, misses = _CACHE_EVENTS["hits"], _CACHE_EVENTS["misses"]
        phase(mx, args.seed, dev.platform)
        say(name + ".done", seconds=round(time.perf_counter() - t0, 2),
            compile_cache_hits=_CACHE_EVENTS["hits"] - hits,
            compile_cache_misses=_CACHE_EVENTS["misses"] - misses)

    say("done", seconds=round(time.perf_counter() - t_start, 2),
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        compile_cache_hits=_CACHE_EVENTS["hits"],
        compile_cache_misses=_CACHE_EVENTS["misses"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
