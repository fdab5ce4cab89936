"""Headline benchmark: ResNet-50 training throughput (images/sec/chip).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N, "rows": [...]}

The headline metric is ResNet-50 bf16 training throughput; `rows` carries the
remaining BASELINE.md configs (inference img/s, LeNet imperative, BERT-base
bf16 fine-tune, INT8-vs-fp32 agreement) measured in the same run.

Baselines (reference's best published single-GPU numbers, BASELINE.md /
docs perf.md:173-253): training fp32 b=128 363.69 img/s; inference fp16
b=128 2355.04 img/s on 1x V100. We train in bf16 (TPU-native dtype, the
AMP policy's default).

Layout: channels-last NHWC (C rides the MXU lane dim; measured faster than
NCHW on v5e — see docs in gluon/nn/conv_layers.py). Override with
MXTPU_BENCH_LAYOUT=NCHW / MXTPU_BENCH_BATCH=N for experiments.

Run on the TPU chip by default; falls back to CPU (honest, slow) if the
chip is unreachable so the driver always gets a JSON line.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

BASELINE_TRAIN_IMG_S = 363.69   # V100 fp32 b=128 training (perf.md:243-253)
BASELINE_INFER_IMG_S = 2355.04  # V100 fp16 b=128 inference (perf.md:198-213)
WARMUP = 3
ITERS = 30


def _latest_bench_snapshot(repo_dir=None):
    """(path, parsed) of the highest-round BENCH_r*.json the driver left
    in the repo root, or (None, None). `parsed` is the prior run's result
    object ({"metric", "value", "rows", ...})."""
    import glob
    import re

    repo_dir = repo_dir or os.path.dirname(os.path.abspath(__file__))
    best, best_round = None, -1
    for path in glob.glob(os.path.join(repo_dir, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m and int(m.group(1)) > best_round:
            best, best_round = path, int(m.group(1))
    if best is None:
        return None, None
    try:
        with open(best) as f:
            snap = json.load(f)
    except (OSError, ValueError):
        return None, None
    parsed = snap.get("parsed") if isinstance(snap, dict) else None
    return best, parsed if isinstance(parsed, dict) else None


def _snapshot_platform(parsed):
    """Platform a bench snapshot was measured on; every run stamps it
    (main() refuses to run anywhere but a TPU)."""
    return str(parsed.get("platform") or "unstamped")


def _check_regressions(current, threshold=0.03):
    """Compare this run's metrics against the latest BENCH_r*.json; any
    same-named metric that regressed more than `threshold` (default 3%)
    gets a WARNING on stderr and a row in the returned list (the r3→r5
    inference regression went unflagged; never again). Throughput metrics
    regress by DROPPING; latency metrics (name containing `_ms`, e.g.
    trainer_update_ms) regress by RISING — the comparison flips
    accordingly. Metric names embed batch and layout, so only
    like-for-like configs compare.

    Snapshots stamped with different platforms never compare: that is a
    platform delta, not a regression — the gate refuses and says so."""
    path, prior = _latest_bench_snapshot()
    if prior is None:
        return []
    prior_platform = _snapshot_platform(prior)
    cur_platform = _snapshot_platform(current)
    if prior_platform != cur_platform:
        note = (f"regression gate skipped: {os.path.basename(path)} was "
                f"measured on {prior_platform!r}, this run on "
                f"{cur_platform!r} — cross-platform deltas are not "
                f"regressions")
        print("note: " + note, file=sys.stderr)
        current["comparison_note"] = note
        return []

    def flatten(result):
        out = {}
        if result.get("metric") and isinstance(
                result.get("value"), (int, float)):
            out[result["metric"]] = float(result["value"])
        for row in result.get("rows") or []:
            if row.get("metric") and isinstance(
                    row.get("value"), (int, float)):
                out[row["metric"]] = float(row["value"])
        return out

    prior_vals, cur_vals = flatten(prior), flatten(current)
    regressions = []
    for name, prev in prior_vals.items():
        cur = cur_vals.get(name)
        if cur is None or prev <= 0 or "agreement" in name:
            continue  # ratios aren't throughput; missing = not comparable
        lower_is_better = (name.endswith("_ms") or "_ms_" in name
                           or name.endswith("_mb") or "_mb_" in name)
        if lower_is_better:
            change = (cur - prev) / prev   # latency rising = regression
        else:
            change = (prev - cur) / prev   # throughput dropping = regression
        if change > threshold:
            regressions.append({
                "metric": name, "previous": prev, "current": cur,
                "drop_pct": round(change * 100, 2),
                "baseline_file": os.path.basename(path),
            })
            print(f"WARNING: {name} regressed {change * 100:.1f}% "
                  f"({prev} -> {cur}) vs {os.path.basename(path)}",
                  file=sys.stderr)
    return regressions


def _timeit(fn, sync, iters, warmup):
    """Time fn() iters times; sync() host-fetches the result, which
    closes the timing on the device's last write."""
    for _ in range(warmup):
        out = fn()
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    sync(out)
    return time.perf_counter() - t0, out


def build_resnet_train(layout, batch, donate=True):
    """Build the ResNet-50 bf16 train step exactly as the bench times it.

    Returns (step, state, x, y) where step(params, momenta, x, y, key) ->
    (new_params, new_momenta, loss). Shared with tools/bench_estimate.py so
    the cost-model artifact analyses the SAME compiled computation the
    on-chip bench runs.
    """
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    mx.seed(0)
    stem_s2d = (os.environ.get("MXTPU_BENCH_S2D", "1") == "1"
                and layout[-1] == "C")
    net = resnet50_v1(classes=1000, layout=layout, stem_s2d=stem_s2d)
    net.initialize()
    amp.convert_hybrid_block(net, target_dtype="bfloat16")

    shape = ((2, 3, 224, 224) if layout == "NCHW" else (2, 224, 224, 3))
    net(mx.np.ones(shape, dtype="bfloat16"))

    fwd, params = net.as_pure_function(training=True)
    trainable = set(net.trainable_param_names())

    rng = jax.random.PRNGKey(0)
    xshape = ((batch, 3, 224, 224) if layout == "NCHW"
              else (batch, 224, 224, 3))
    x = jax.random.normal(rng, xshape, jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, 1000)
    # MXTPU_BENCH_MP=1 (default): momentum kept in f32 — the reference's
    # mp_sgd master-state semantics (r4 HLO audit patch A). bf16 momentum
    # storage loses ~8 mantissa bits per step AND adds two casts per
    # param; f32 adds 50 MB of state on a 25M-param net. =0 reverts for
    # an on-chip A/B.
    mp = os.environ.get("MXTPU_BENCH_MP", "1") == "1"
    mom_dtype = jnp.float32 if mp else None
    momenta = {n: jnp.zeros_like(a, dtype=mom_dtype)
               for n, a in params.items() if n in trainable}

    def train_step(params, momenta, x, y, key):
        def loss_fn(pd):
            out, new_pd = fwd(pd, key, x)
            logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()
            return nll, new_pd

        (loss, new_pd), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params = {}
        new_mom = {}
        for n, p in params.items():
            if n in momenta:
                g = grads[n].astype(jnp.float32)
                m = 0.9 * momenta[n].astype(jnp.float32) - 0.1 * g
                new_mom[n] = m.astype(momenta[n].dtype)
                new_params[n] = (p.astype(jnp.float32) + m).astype(p.dtype)
            else:
                new_params[n] = new_pd[n]
        return new_params, new_mom, loss

    step = jax.jit(train_step, donate_argnums=(0, 1) if donate else ())
    return net, step, params, momenta, x, y


def bench_resnet_train(platform, layout, batch, iters, warmup):
    import jax
    import jax.numpy as jnp

    net, step, params, momenta, x, y = build_resnet_train(layout, batch)
    rng = jax.random.PRNGKey(0)
    xshape = x.shape

    state = {"params": params, "momenta": momenta}
    keys = [jax.random.PRNGKey(100 + i) for i in range(iters + warmup)]
    ki = iter(keys)

    def one():
        state["params"], state["momenta"], loss = step(
            state["params"], state["momenta"], x, y, next(ki))
        return loss

    dt, loss = _timeit(one, lambda l: float(l), iters, warmup)
    if not math.isfinite(float(loss)):
        raise SystemExit(f"non-finite training loss {float(loss)}")
    train_img_s = batch * iters / dt

    # inference on the same net (predict-mode jit over the trained params —
    # the originals were donated into the train step)
    infer_batch = batch
    xi = jax.random.normal(rng, xshape, jnp.bfloat16)
    pfwd, _ = net.as_pure_function(training=False)
    pparams = state["params"]

    @jax.jit
    def predict(p, x):
        return jnp.argmax(pfwd(p, None, x)[0], axis=-1)

    def one_inf():
        return predict(pparams, xi)

    dt_i, out = _timeit(lambda: one_inf(), lambda o: int(o[0]),
                        iters, warmup)
    infer_img_s = infer_batch * iters / dt_i
    return train_img_s, infer_img_s


def bench_lenet_imperative(platform, iters, warmup):
    """LeNet-MNIST imperative (no jit of the user loop — the BASELINE
    config #1 'imperative mode' row). Uses the framework's eager NDArray
    path end to end."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo.vision import lenet

    mx.seed(0)
    net = lenet(classes=10)
    net.initialize()
    batch = 256
    x = mx.np.array(__import__("numpy").random.rand(
        batch, 1, 28, 28).astype("float32"))
    y = mx.np.array(__import__("numpy").random.randint(
        0, 10, (batch,)))
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})

    def one():
        with autograd.record():
            loss = lossfn(net(x), y)
        loss.backward()
        trainer.step(batch)
        return loss

    dt, loss = _timeit(one, lambda l: float(l.sum().asnumpy()),
                       iters, warmup)
    return batch * iters / dt


def build_bert_finetune(batch=8, seq=384, donate=True):
    """Build the BERT-base bf16 fine-tune step exactly as the bench times
    it (SQuAD-style QA head). Shared with tools/bench_estimate.py."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.gluon.model_zoo.bert import BERTForQA, bert_12_768_12

    mx.seed(0)
    net = BERTForQA(bert_12_768_12(vocab_size=30522, dropout=0.1))
    net.initialize()
    amp.convert_hybrid_block(net, target_dtype="bfloat16")
    import numpy as onp

    tok = mx.np.array(onp.random.randint(0, 30000, (2, seq)))
    seg = mx.np.zeros((2, seq), dtype="int32")
    net(tok, seg)

    fwd, params = net.as_pure_function(training=True)
    trainable = set(net.trainable_param_names())
    tokens = jnp.asarray(onp.random.randint(0, 30000, (batch, seq)))
    segments = jnp.zeros((batch, seq), jnp.int32)
    starts = jnp.asarray(onp.random.randint(0, seq, (batch,)))
    ends = jnp.asarray(onp.random.randint(0, seq, (batch,)))

    def step_fn(params, key):
        def loss_fn(pd):
            (s_logits, e_logits), new_pd = fwd(pd, key, tokens, segments)
            s_logp = jax.nn.log_softmax(s_logits.astype(jnp.float32), -1)
            e_logp = jax.nn.log_softmax(e_logits.astype(jnp.float32), -1)
            nll = -(jnp.take_along_axis(s_logp, starts[:, None], 1).mean()
                    + jnp.take_along_axis(e_logp, ends[:, None], 1).mean())
            return nll, new_pd

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new = {n: (p - 1e-5 * grads[n].astype(p.dtype)
                   if n in trainable else p)
               for n, p in params.items()}
        return new, loss

    step = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
    return step, params


def bench_bert_finetune(platform, iters, warmup):
    """BERT-base bf16 fine-tune step throughput (BASELINE config #4:
    SQuAD-style QA head, seq 384, bf16)."""
    import jax

    batch = 8
    step, params = build_bert_finetune(batch=batch)
    state = {"p": params}
    keys = [jax.random.PRNGKey(i) for i in range(iters + warmup)]
    ki = iter(keys)

    def one():
        state["p"], loss = step(state["p"], next(ki))
        return loss

    dt, loss = _timeit(one, lambda l: float(l), iters, warmup)
    if not math.isfinite(float(loss)):
        raise SystemExit("non-finite BERT loss")
    return batch * iters / dt


def bench_int8_agreement(platform):
    """INT8-vs-fp32 top-1 agreement for quantized ResNet-18 on a fixed
    synthetic eval set (no ImageNet in the image: agreement rate stands in
    for the reference's accuracy-delta table,
    example/quantization/README.md:113-121 — fp32 76.36 vs int8 76.04
    top-1, i.e. ~99.6% relative)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.contrib import quantization as q
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    mx.seed(0)
    net = resnet18_v1(classes=100)
    net.initialize()
    rs = onp.random.RandomState(0)
    calib = [mx.np.array(rs.rand(8, 3, 32, 32).astype("f"))
             for _ in range(4)]
    qnet = q.quantize_net(net, calib_data=calib, calib_mode="entropy")
    agree = 0
    total = 0
    for _ in range(8):
        x = mx.np.array(rs.rand(16, 3, 32, 32).astype("f"))
        ref = net(x).asnumpy().argmax(-1)
        got = qnet(x).asnumpy().argmax(-1)
        agree += int((ref == got).sum())
        total += ref.size
    return agree / total


def _resnet50_param_shapes():
    """Conv/BN/FC tensor shapes of ResNet-50 v1 (161 tensors, ~25.6M
    params) — synthesized so the update bench measures ONLY the trainer's
    fused optimizer dispatch, not model build/compile time."""
    shapes = [(64, 7, 7, 3), (64,), (64,)]
    in_c = 64
    for blocks, width in [(3, 64), (4, 128), (6, 256), (3, 512)]:
        for b in range(blocks):
            out_c = width * 4
            shapes += [(width, 1, 1, in_c), (width,), (width,)]
            shapes += [(width, 3, 3, width), (width,), (width,)]
            shapes += [(out_c, 1, 1, width), (out_c,), (out_c,)]
            if b == 0:
                shapes += [(out_c, 1, 1, in_c), (out_c,), (out_c,)]
            in_c = out_c
    shapes += [(1000, 2048), (1000,)]
    return shapes


def bench_trainer_update_ms(platform, steps=50):
    """Milliseconds per fused Trainer.update over a ResNet-50-shaped
    param set (161 tensors, SGD momentum): the dispatch-tax row the
    fused multi-tensor path exists to shrink (docs/performance.md).
    One bucket → one donated jit dispatch per step; the legacy loop
    would pay ~161."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    mx.seed(0)
    rs = onp.random.RandomState(0)
    params = []
    for k, shape in enumerate(_resnet50_param_shapes()):
        p = gluon.Parameter(f"p{k}", shape=shape)
        p.initialize()
        params.append(p)
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9})
    for p in params:
        g = p.grad()
        g._data = mx.np.array(
            rs.standard_normal(p.shape).astype("f"))._data
        g._version += 1

    def sync():
        params[0].data().asnumpy()

    trainer.update(1)   # absorb trace + compile
    sync()
    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.update(1)
    sync()
    return (time.perf_counter() - t0) / steps * 1000.0


def bench_whole_step(platform, iters, warmup):
    """A/B of the one-dispatch whole-step path vs the legacy three-phase
    sequence on the SAME model/loss/optimizer: gluon.TrainStep (forward +
    backward + fused update in ONE donated jit dispatch) against
    record/backward/Trainer.step. Returns (whole_ms, phased_ms, img_s).
    ResNet-50 on an accelerator; a Dense stack when called with
    platform="cpu" (tests) so the row stays cheap (the dispatch-count delta it measures exists on CPU
    too). Lower _ms is better — the >3% regression gate inverts."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    if platform != "cpu":
        from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

        batch = int(os.environ.get("MXTPU_BENCH_BATCH", "64"))
        xshape, classes = (batch, 224, 224, 3), 1000

        def build_net():
            return resnet50_v1(classes=classes, layout="NHWC")
    else:
        batch = 32
        xshape, classes = (batch, 128), 10

        def build_net():
            net = nn.HybridSequential()
            net.add(nn.Dense(256, activation="relu"), nn.Dense(64),
                    nn.Dense(classes))
            return net

    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(*xshape).astype("f"))
    y = mx.np.array(rs.randint(0, classes, (batch,)))
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()

    def build():
        mx.seed(0)
        net = build_net()
        net.initialize()
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9})
        return net, trainer

    # A: whole-step (one donated dispatch per step)
    net, trainer = build()
    step = gluon.TrainStep(net, lossfn, trainer)
    dt_w, loss = _timeit(lambda: step(x, y),
                         lambda l: float(l.sum().asnumpy()),
                         iters, warmup)
    if step.last_path != "whole_step":
        raise RuntimeError("whole-step path fell back to phased: "
                           f"{step.ineligible_reason()}")
    if not math.isfinite(float(loss.sum().asnumpy())):
        raise SystemExit("non-finite whole-step loss")

    # B: legacy three-phase sequence, same everything
    net, trainer = build()

    def phased():
        with autograd.record():
            loss = lossfn(net(x), y)
        loss.backward()
        trainer.step(batch)
        return loss

    dt_p, _ = _timeit(phased, lambda l: float(l.sum().asnumpy()),
                      iters, warmup)
    return (dt_w / iters * 1000.0, dt_p / iters * 1000.0,
            batch * iters / dt_w)


def bench_numerics_overhead(platform, iters, warmup):
    """Whole-step latency with MXTPU_NUMERICS=step vs off on the same
    model: the in-graph is-finite AND-reduce plus its async callback
    (docs/observability.md). Returns (step_mode_ms, off_ms). The
    acceptance bar is <=3% overhead; the note carries the ratio."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    batch = 32 if platform == "cpu" else 128
    feats, classes = (128, 10) if platform == "cpu" else (512, 100)
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(batch, feats).astype("f"))
    y = mx.np.array(rs.randint(0, classes, (batch,)))
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(numerics_mode):
        prev = os.environ.get("MXTPU_NUMERICS")
        os.environ["MXTPU_NUMERICS"] = numerics_mode
        try:
            mx.seed(0)
            net = nn.HybridSequential()
            net.add(nn.Dense(256, activation="relu"), nn.Dense(256),
                    nn.Dense(classes))
            net.initialize()
            net.hybridize()
            trainer = gluon.Trainer(net.collect_params(), "sgd",
                                    {"learning_rate": 0.05})
            step = gluon.TrainStep(net, lossfn, trainer)
            dt, _ = _timeit(lambda: step(x, y),
                            lambda l: float(l.sum().asnumpy()),
                            iters, warmup)
            if step.last_path != "whole_step":
                raise RuntimeError("numerics bench fell back to phased")
            return dt / iters * 1000.0
        finally:
            if prev is None:
                os.environ.pop("MXTPU_NUMERICS", None)
            else:
                os.environ["MXTPU_NUMERICS"] = prev

    off_ms = run("off")
    step_ms = run("step")
    return step_ms, off_ms


def bench_kernels_overhead(platform, iters, warmup):
    """Whole-step latency with MXTPU_KERNELS=auto vs 0 on a BN-heavy
    model (Dense→BatchNorm→Dense, multi-precision SGD — both kernel
    families eligible). Returns (kernels_ms, off_ms). On CPU the auto
    dispatch declines on platform and both sides run the XLA path — the
    row then measures dispatch overhead only (tests call it that way;
    main() runs on a TPU); docs/kernels.md has the on-chip expectations."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    batch = 32 if platform == "cpu" else 256
    feats, classes = (128, 10) if platform == "cpu" else (512, 100)
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(batch, feats).astype("f"), dtype="bfloat16")
    y = mx.np.array(rs.randint(0, classes, (batch,)))
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(kernels_mode):
        prev = os.environ.get("MXTPU_KERNELS")
        os.environ["MXTPU_KERNELS"] = kernels_mode
        try:
            mx.seed(0)
            net = nn.HybridSequential()
            net.add(nn.Dense(256, activation="relu"), nn.BatchNorm(),
                    nn.Dense(classes))
            net.initialize()
            net.cast("bfloat16")
            net.hybridize()
            trainer = gluon.Trainer(
                net.collect_params(), "sgd",
                {"learning_rate": 0.05, "momentum": 0.9,
                 "multi_precision": True})
            step = gluon.TrainStep(net, lossfn, trainer)
            dt, _ = _timeit(lambda: step(x, y),
                            lambda l: float(l.sum().asnumpy()),
                            iters, warmup)
            if step.last_path != "whole_step":
                raise RuntimeError("kernels bench fell back to phased")
            return dt / iters * 1000.0
        finally:
            if prev is None:
                os.environ.pop("MXTPU_KERNELS", None)
            else:
                os.environ["MXTPU_KERNELS"] = prev

    off_ms = run("0")
    kernels_ms = run("auto")
    return kernels_ms, off_ms


def bench_layout_overhead(platform, iters, warmup):
    """Whole-step latency with MXTPU_LAYOUT=auto vs off on an NCHW
    conv/BN/relu stack (the LayoutPass target shape). Returns
    (auto_ms, off_ms, img_s_auto). On CPU both sides run the same math
    (XLA layout-assigns either way) — the row then measures rewrite +
    re-layout overhead only; on TPU the
    auto side keeps C in lanes end to end (docs/layout.md)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    batch = 4 if platform == "cpu" else 64
    side = 16 if platform == "cpu" else 56
    widths = (32, 64) if platform == "cpu" else (128, 256, 256)
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(batch, 16, side, side).astype("f"))
    y = mx.np.array(
        rs.rand(batch, widths[-1], side, side).astype("f"))

    def run(layout_mode):
        prev = os.environ.get("MXTPU_LAYOUT")
        os.environ["MXTPU_LAYOUT"] = layout_mode
        try:
            mx.seed(0)
            net = nn.HybridSequential()
            c_in = 16
            for c in widths:
                net.add(nn.Conv2D(c, 3, padding=1, in_channels=c_in,
                                  use_bias=False),
                        nn.BatchNorm(in_channels=c),
                        nn.Activation("relu"))
                c_in = c
            net.initialize()
            net.hybridize()
            trainer = gluon.Trainer(
                net.collect_params(), "sgd",
                {"learning_rate": 0.05, "momentum": 0.9})
            step = gluon.TrainStep(
                net, lambda out, t: ((out - t) ** 2).mean(), trainer)
            dt, _ = _timeit(lambda: step(x, y),
                            lambda l: float(l.asnumpy()),
                            iters, warmup)
            if step.last_path != "whole_step":
                raise RuntimeError("layout bench fell back to phased")
            return dt / iters * 1000.0
        finally:
            if prev is None:
                os.environ.pop("MXTPU_LAYOUT", None)
            else:
                os.environ["MXTPU_LAYOUT"] = prev

    off_ms = run("off")
    auto_ms = run("auto")
    img_s_auto = batch / (auto_ms / 1000.0)
    return auto_ms, off_ms, img_s_auto


def _sharding_bench_run(batch, feats, classes, iters, warmup):
    """Inner dp8 measurement — needs >=8 visible devices (the CPU row
    re-launches it in a subprocess with forced virtual devices). Times
    the one-time ShardingPlan placement and `iters` donated whole-step
    dispatches over Trainer(mesh=(('dp', -1),))."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.sharding import ShardingPlan

    mx.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(512, activation="relu", in_units=feats),
            gluon.nn.Dense(classes, in_units=512))
    net.initialize()
    net.hybridize()
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(batch, feats).astype("f"))
    y = mx.np.array(rs.randint(0, classes, (batch,)).astype("i4"))

    plan = ShardingPlan("dp=-1")
    t0 = time.perf_counter()
    plan.apply(dict(net.collect_params()), label="bench")
    apply_ms = (time.perf_counter() - t0) * 1000.0

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="tpu_dist", sharding_plan=plan)
    step = gluon.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)
    dt, _ = _timeit(lambda: step(x, y),
                    lambda l: float(l.asnumpy().sum()), iters, warmup)
    if step.last_path != "whole_step":
        raise RuntimeError(
            f"dp8 bench fell back to phased: {step.ineligible_reason()}")
    return {"img_s": batch * iters / dt, "apply_ms": apply_ms}


def bench_sharding(platform, iters, warmup):
    """dp8 whole-step throughput + one-time plan placement cost
    (docs/sharding.md). The 8-way CPU mesh needs the process-level
    --xla_force_host_platform_device_count flag, so on CPU the
    measurement runs in a subprocess; accelerators use the first 8
    real devices in-process."""
    batch = 64 if platform == "cpu" else 256
    feats, classes = (256, 10) if platform == "cpu" else (512, 100)
    if platform == "cpu":
        import subprocess

        flags = (os.environ.get("XLA_FLAGS", "") +
                 " --xla_force_host_platform_device_count=8").strip()
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags)
        out = subprocess.run(
            [sys.executable, "-c",
             "import json, bench; print(json.dumps("
             f"bench._sharding_bench_run({batch}, {feats}, {classes}, "
             f"{iters}, {warmup})))"],
            capture_output=True, text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env)
        if out.returncode != 0:
            raise RuntimeError(out.stderr.strip()[-400:])
        res = json.loads(out.stdout.strip().splitlines()[-1])
    else:
        import jax

        ndev = len(jax.devices())
        if ndev < 8:
            raise RuntimeError(f"dp8 needs 8 devices, have {ndev}")
        res = _sharding_bench_run(batch, feats, classes, iters, warmup)
    return res["img_s"], res["apply_ms"]


def _hybrid_bench_run(batch, feats, classes, iters, warmup):
    """Inner dp4 x tp2 + ZeRO measurement — needs >=8 visible devices
    (CPU re-launches in a subprocess, like _sharding_bench_run). Times
    the donated whole-step GSPMD program on the SpecLayout hybrid plan,
    then sizes per-device optimizer state under fsdp=4 vs replicated."""
    import jax
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.sharding import ShardingPlan

    def build(axes):
        mx.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(512, activation="relu", in_units=feats),
                gluon.nn.Dense(classes, in_units=512))
        net.initialize()
        net.hybridize()
        plan = ShardingPlan.from_layout(axes, net=net) if axes else None
        kw = (dict(kvstore="tpu_dist", sharding_plan=plan) if plan
              else {})
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9},
                                **kw)
        step = gluon.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)
        return net, trainer, step

    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(batch, feats).astype("f"))
    y = mx.np.array(rs.randint(0, classes, (batch,)).astype("i4"))

    _net, _tr, step = build("dp=4,tp=2")
    dt, _ = _timeit(lambda: step(x, y),
                    lambda l: float(l.asnumpy().sum()), iters, warmup)
    if step.last_path != "whole_step":
        raise RuntimeError(
            f"tp2dp4 bench fell back: {step.ineligible_reason()}")

    def state_mb(trainer):
        total = 0
        for st in trainer._states:
            for v in jax.tree_util.tree_leaves(st):
                d = getattr(v, "_data", v)
                if hasattr(d, "addressable_shards"):
                    s = d.addressable_shards[0].data
                    total += s.size * s.dtype.itemsize
        return total / 1e6

    _netz, trz, stepz = build("dp=2,fsdp=4")
    stepz(x, y)
    if stepz.last_path != "whole_step":
        raise RuntimeError(
            f"fsdp4 bench fell back: {stepz.ineligible_reason()}")
    _netr, trr, stepr = build(None)
    stepr(x, y)
    return {"img_s": batch * iters / dt,
            "opt_state_mb": state_mb(trz),
            "opt_state_mb_repl": state_mb(trr)}


def bench_hybrid(platform, iters, warmup):
    """dp4 x tp2 whole-step throughput + per-device ZeRO optimizer
    state (docs/sharding.md). Same subprocess dance as bench_sharding
    for the forced 8-way CPU mesh."""
    batch = 64 if platform == "cpu" else 256
    feats, classes = (256, 16) if platform == "cpu" else (512, 128)
    if platform == "cpu":
        import subprocess

        flags = (os.environ.get("XLA_FLAGS", "") +
                 " --xla_force_host_platform_device_count=8").strip()
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags)
        out = subprocess.run(
            [sys.executable, "-c",
             "import json, bench; print(json.dumps("
             f"bench._hybrid_bench_run({batch}, {feats}, {classes}, "
             f"{iters}, {warmup})))"],
            capture_output=True, text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env)
        if out.returncode != 0:
            raise RuntimeError(out.stderr.strip()[-400:])
        return json.loads(out.stdout.strip().splitlines()[-1])
    import jax

    ndev = len(jax.devices())
    if ndev < 8:
        raise RuntimeError(f"tp2dp4 needs 8 devices, have {ndev}")
    return _hybrid_bench_run(batch, feats, classes, iters, warmup)


def bench_kernel_micro_ms(platform, iters=50):
    """Per-kernel microbenches at an audited shape: wall ms per call of
    the BN statistics forward, the BN backward, and the fused optimizer
    ladder, each through its dispatching entry point (kernel on TPU;
    off a TPU the dispatch records outcome `platform` and the XLA twin
    runs). Returns {"bn_fwd": ms, "bn_bwd": ms, "opt": ms}."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import norm as knorm
    from mxnet_tpu.kernels import opt as kopt
    from mxnet_tpu.optimizer import SGD

    prev = os.environ.get("MXTPU_KERNELS")
    os.environ["MXTPU_KERNELS"] = "auto"
    try:
        m = 2048 if platform != "cpu" else 256
        c = 512
        x = jnp.ones((m, c), jnp.bfloat16)
        g = jnp.ones((c,), jnp.float32)
        b = jnp.zeros((c,), jnp.float32)
        s = jnp.zeros((c,), jnp.float32)

        fwd = jax.jit(lambda x_: knorm.bn_train(x_, g, b, s, 1e-5, 1))
        grad = jax.jit(jax.grad(
            lambda x_: knorm.bn_train(x_, g, b, s, 1e-5, 1)[0]
            .astype(jnp.float32).sum()))

        n = (1 << 20) if platform != "cpu" else (1 << 16)
        w = jnp.ones((n,), jnp.bfloat16)
        gw = jnp.ones((n,), jnp.bfloat16)
        master = jnp.ones((n,), jnp.float32)
        mom = jnp.zeros((n,), jnp.float32)
        hyper = {"momentum": 0.9, "rescale_grad": 1.0}
        opt = jax.jit(lambda w_, ma, mo, g_: kopt.param_step(
            SGD, None, False, True, w_, (ma, mo), g_, 0.01, 1e-4, 1,
            None, hyper))

        out = {}
        for name, fn, sync in (
                ("bn_fwd", lambda: fwd(x), lambda r: r[0].block_until_ready()),
                ("bn_bwd", lambda: grad(x), lambda r: r.block_until_ready()),
                ("opt", lambda: opt(w, master, mom, gw),
                 lambda r: r[0].block_until_ready())):
            dt, _ = _timeit(fn, sync, iters, 3)
            out[name] = dt / iters * 1000.0
        return out
    finally:
        if prev is None:
            os.environ.pop("MXTPU_KERNELS", None)
        else:
            os.environ["MXTPU_KERNELS"] = prev


def bench_flightrec_record_ms(records=1000):
    """Steady-state flight-recorder cost: wall ms per `records` record()
    calls into a full ring (the hot-path budget — one dict build + one
    deque append + one counter bump per event)."""
    from mxnet_tpu.observability import flight

    flight.reset()
    for i in range(flight.capacity()):  # steady state: ring already full
        flight.record("warm", i=i)
    t0 = time.perf_counter()
    for i in range(records):
        flight.record("bench", i=i, value=1.5)
    dt = time.perf_counter() - t0
    flight.reset()
    return dt * 1000.0


def bench_opsd_overhead(platform, iters, warmup):
    """Whole-step latency with the live ops server up AND a 10 Hz
    /metrics scraper attached, vs no server at all (the MXTPU_OPS_PORT
    unset baseline). Returns (opsd_ms, off_ms, scrape_ms): the A/B
    proves a polled ops plane doesn't tax the donated training path
    (GETs only read snapshots), and scrape_ms is the cost of one full
    /metrics round-trip on a warm registry (docs/observability.md)."""
    import threading
    import time as _time
    import urllib.request

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.observability import opsd
    from mxnet_tpu.telemetry import promparse

    batch = 32 if platform == "cpu" else 128
    feats, classes = (128, 10) if platform == "cpu" else (512, 100)
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(batch, feats).astype("f"))
    y = mx.np.array(rs.randint(0, classes, (batch,)))
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(with_server):
        mx.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(256, activation="relu"), nn.Dense(256),
                nn.Dense(classes))
        net.initialize()
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05})
        step = gluon.TrainStep(net, lossfn, trainer)
        srv = scraper = None
        stop = threading.Event()
        if with_server:
            srv = opsd.OpsServer(port=0).start()

            def poll():  # the 10 Hz supervisor this bench models
                while not stop.is_set():
                    with urllib.request.urlopen(srv.url + "/metrics",
                                                timeout=5) as r:
                        promparse.parse_text(r.read().decode())
                    stop.wait(0.1)

            scraper = threading.Thread(target=poll, daemon=True)
            scraper.start()
        try:
            dt, _ = _timeit(lambda: step(x, y),
                            lambda l: float(l.sum().asnumpy()),
                            iters, warmup)
            if step.last_path != "whole_step":
                raise RuntimeError("opsd bench fell back to phased")
            return dt / iters * 1000.0
        finally:
            stop.set()
            if scraper is not None:
                scraper.join(timeout=10)
            if srv is not None:
                srv.stop()

    off_ms = run(False)
    opsd_ms = run(True)

    # one /metrics GET on the registry the A/B just populated
    srv = opsd.OpsServer(port=0).start()
    try:
        n = 20
        t0 = _time.perf_counter()
        for _ in range(n):
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=5) as r:
                r.read()
        scrape_ms = (_time.perf_counter() - t0) / n * 1000.0
    finally:
        srv.stop()
    return opsd_ms, off_ms, scrape_ms


def bench_ckpt_save_ms(platform, saves=3):
    """Milliseconds per committed checkpoint of ResNet-50-sized training
    state (161 param tensors + SGD-momentum state, ~205 MB of f32)
    through the async engine path: CheckpointManager.save() + flush(),
    capture through fsync'd rename (docs/checkpointing.md). Lower is
    better; the >3% regression gate applies via the _ms suffix."""
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    mx.seed(0)
    rs = onp.random.RandomState(0)
    params = []
    for k, shape in enumerate(_resnet50_param_shapes()):
        p = gluon.Parameter(f"p{k}", shape=shape)
        p.initialize()
        params.append(p)
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9})
    for p in params:
        g = p.grad()
        g._data = mx.np.array(
            rs.standard_normal(p.shape).astype("f"))._data
        g._version += 1
    trainer.update(1)   # materialize momentum state
    params[0].data().asnumpy()

    ckdir = tempfile.mkdtemp(prefix="bench-ckpt-")
    try:
        mgr = mx.checkpoint.CheckpointManager(
            ckdir, trainer, keep_last=1, async_save=True)
        mgr.save(step=0)
        mgr.flush()     # warm: page cache, npz codepaths
        t0 = time.perf_counter()
        for s in range(1, saves + 1):
            mgr.save(step=s)
            mgr.flush()
        return (time.perf_counter() - t0) / saves * 1000.0
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


def bench_reshard_restore_ms(platform, restores=3):
    """Milliseconds per mesh-migrating restore: a dp=4 checkpoint
    restored onto a dp=2 trainer with allow_reshard=True — manifest
    read + plan-compatibility judgment + host arrays re-placed under
    the new plan's NamedShardings (docs/elasticity.md). Lower is
    better; the >3% regression gate applies via the _ms suffix."""
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.sharding import ShardingPlan

    def build(axes):
        mx.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(256, activation="relu"),
                gluon.nn.Dense(64))
        net.initialize()
        net.hybridize()
        plan = ShardingPlan(axes)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9},
                                kvstore="tpu_dist", sharding_plan=plan)
        step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
        rs = onp.random.RandomState(3)
        x = mx.np.array(rs.standard_normal((32, 128)).astype("f"))
        y = mx.np.array(rs.standard_normal((32, 64)).astype("f"))
        step(x, y)
        return trainer

    ckdir = tempfile.mkdtemp(prefix="bench-reshard-")
    try:
        mgr4 = mx.checkpoint.CheckpointManager(ckdir, build("dp=4"))
        mgr4.save(step=1)
        mgr4.flush()
        tr2 = build("dp=2")
        mgr2 = mx.checkpoint.CheckpointManager(ckdir, tr2)
        mgr2.restore(allow_reshard=True)   # warm: npz read, placement
        t0 = time.perf_counter()
        for _ in range(restores):
            mgr2.restore(allow_reshard=True)
        return (time.perf_counter() - t0) / restores * 1000.0
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


def bench_serving_qps(platform, clients=8, requests=40,
                      trace_sample=None):
    """Serving-engine round-trip QPS: `clients` threads hammering one
    dynamically-batching InferenceEngine through warmup()ed buckets
    (docs/serving.md). A small MLP keeps the row cheap enough to measure
    when tests call it on the CPU too — the number tracks the engine's
    queue/batch/dispatch overhead and cache-hit dispatch, not model
    FLOPs. Raises if any served shape recompiled after warmup.

    trace_sample pins MXTPU_TRACE_SAMPLE for the run (restored after) —
    the serve_qps_traced row A/Bs 0.1 head sampling against off."""
    import threading

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon import nn

    prev = os.environ.get("MXTPU_TRACE_SAMPLE")
    if trace_sample is not None:
        os.environ["MXTPU_TRACE_SAMPLE"] = str(trace_sample)
    try:
        return _bench_serving_qps_run(
            mx, serving, nn, onp, threading, clients, requests)
    finally:
        if trace_sample is not None:
            if prev is None:
                os.environ.pop("MXTPU_TRACE_SAMPLE", None)
            else:
                os.environ["MXTPU_TRACE_SAMPLE"] = prev


def _bench_serving_qps_run(mx, serving, nn, onp, threading, clients,
                           requests):
    mx.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu"), nn.Dense(64))
    net.initialize()
    net.hybridize()
    eng = serving.InferenceEngine(
        net, name="bench_mlp", max_batch_size=16, max_wait_ms=1.0,
        timeout_ms=30_000.0)
    eng.warmup(mx.np.zeros((1, 128)))
    rs = onp.random.RandomState(0)
    xs = [onp.asarray(rs.rand(1, 128), onp.float32) for _ in range(8)]
    errs = []

    def client(i):
        try:
            for k in range(requests):
                eng.predict(xs[(i + k) % len(xs)])
        except Exception as e:  # noqa: BLE001 — surfaced via errs below
            errs.append(e)

    with eng:
        eng.predict(xs[0])  # absorb first-dispatch overheads
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
    if errs:
        raise errs[0]
    recompiles = eng.recompiles_since_warmup()
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompile(s) after warmup — serving bench "
            "measured compile time, not serving throughput")
    return clients * requests / dt


def bench_decode(platform, sequences=16, new_tokens=24):
    """KV-cache decode throughput + TTFT through the DecodeEngine
    (docs/decode.md): `sequences` streamed sequences over TinyCausalLM
    with continuous slot churn. Returns (tok_s, ttft_p50_ms). Cheap by
    construction (tiny model, CPU-honest); the engine raises on any
    recompile after warmup, so the row measures steady-state stepping,
    never compiles. decode_tok_s rides the higher-is-better gate and
    decode_ttft_ms the lower-is-better gate."""
    import threading

    from mxnet_tpu.decode import DecodeEngine, TinyCausalLM

    lm = TinyCausalLM(max_len=128)
    eng = DecodeEngine(lm, name="bench_decode", num_slots=4,
                       max_wait_ms=1.0, timeout_ms=60_000.0)
    eng.warmup()
    ttft = []
    tokens = [0]
    lock = threading.Lock()

    def consume(seq, t0):
        n = 0
        for _ in seq.stream():
            if n == 0:
                first = time.perf_counter() - t0
            n += 1
        with lock:
            ttft.append(first)
            tokens[0] += n

    with eng:
        # absorb first-dispatch overheads before timing
        eng.submit([1, 2], max_new_tokens=2).result()
        t0 = time.perf_counter()
        threads = []
        for k in range(sequences):
            prompt = [1 + (k + j) % 50 for j in range(1 + k % 8)]
            seq = eng.submit(prompt, max_new_tokens=new_tokens)
            t = threading.Thread(target=consume,
                                 args=(seq, time.perf_counter()),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=120)
        dt = time.perf_counter() - t0
    recompiles = eng.recompiles_since_warmup()
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompile(s) after warmup — decode bench "
            "measured compile time, not token generation")
    if len(ttft) != sequences:
        raise RuntimeError(
            f"only {len(ttft)}/{sequences} sequences completed")
    ttft.sort()
    return tokens[0] / dt, ttft[len(ttft) // 2] * 1000.0


def bench_passes_compile_ms(platform):
    """Wall-ms of one pipeline build (trace + AMP pass + dedup hashing +
    XLA compile) of a small MLP through the graph-pass seam
    (docs/passes.md). Lower is better via the _ms suffix: a pass-manager
    overhead regression shows up here before it taxes every rebuild."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.gluon import nn

    prev = os.environ.get("MXTPU_GRAPH_DEDUP")
    os.environ["MXTPU_GRAPH_DEDUP"] = "1"
    try:
        mx.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(256, activation="relu"), nn.Dense(64))
        net.initialize()
        net.hybridize()
        amp.convert_hybrid_block(net, graph_pass=True)
        x = mx.np.array(onp.random.RandomState(0).rand(8, 128)
                        .astype("f"))
        t0 = time.perf_counter()
        net(x).asnumpy()
        return (time.perf_counter() - t0) * 1000.0
    finally:
        # later rows (peak_hbm_mb reads the whole compile registry) must
        # not silently inherit the dedup path
        if prev is None:
            del os.environ["MXTPU_GRAPH_DEDUP"]
        else:
            os.environ["MXTPU_GRAPH_DEDUP"] = prev


def bench_peak_hbm_mb(platform):
    """Largest reported program footprint (MB) across the compile
    registry after this run's benches: prefers the backend-independent
    liveness peak (peak_live_bytes, passes/memory.py), falls back to
    XLA's memory_analysis sum. A >3% RISE trips the regression gate via
    the _mb suffix — this is the row the remat pass exists to bend."""
    from mxnet_tpu import diagnostics

    best = 0
    for e in diagnostics.compile_registry().values():
        v = e.get("peak_live_bytes") or e.get("peak_hbm_bytes") or 0
        best = max(best, int(v))
    if not best:
        raise RuntimeError("no compile-registry entries with memory "
                           "info (MXTPU_DIAG_COMPILE=0?)")
    return best / (1 << 20)


def main():
    import jax

    t_start = time.perf_counter()  # budget covers the WHOLE run
    # one process, no probe child (a chip belongs to one process), and
    # no CPU branch: a benchmark that finds no chip has nothing to say
    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu":
        raise RuntimeError(
            f"bench.py measures on a TPU; this process runs on "
            f"{platform!r} ({dev.device_kind}). CPU runs prove counts "
            f"and correctness in tests/, never a time or a rate.")

    # liveness peaks in the compile registry are opt-in; the
    # peak_hbm_mb row prefers them over XLA's temp-sum (see
    # bench_peak_hbm_mb), so turn them on for the whole run
    os.environ.setdefault("MXTPU_DIAG_MEMORY", "1")

    layout = os.environ.get("MXTPU_BENCH_LAYOUT", "NHWC")
    batch = int(os.environ.get("MXTPU_BENCH_BATCH", "256"))
    iters = ITERS
    warmup = WARMUP

    train_img_s, infer_img_s = bench_resnet_train(
        platform, layout, batch, iters, warmup)

    rows = [{
        "metric": f"resnet50_infer_bf16_b{batch}_imgs_per_sec_per_chip",
        "value": round(infer_img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(infer_img_s / BASELINE_INFER_IMG_S, 4),
    }, {
        # stable alias of the row above: the name doesn't embed batch or
        # layout, so _check_regressions compares it across runs even when
        # those knobs change
        "metric": "inference_img_s",
        "value": round(infer_img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(infer_img_s / BASELINE_INFER_IMG_S, 4),
    }]
    # stop adding secondary rows once the wall-clock budget is spent
    budget_s = float(os.environ.get("MXTPU_BENCH_BUDGET_S", "1200"))

    def over_budget():
        return time.perf_counter() - t_start > budget_s

    secondary_wanted = os.environ.get("MXTPU_BENCH_HEADLINE_ONLY") != "1"
    if secondary_wanted and over_budget():
        rows.append({"metric": "secondary_benches",
                     "error": "bench budget exhausted before "
                              "lenet/bert/int8 rows"})
    if secondary_wanted and not over_budget():
        try:
            lenet_img_s = bench_lenet_imperative(
                platform, iters, warmup)
            rows.append({
                "metric": "lenet_mnist_imperative_imgs_per_sec",
                "value": round(lenet_img_s, 2), "unit": "img/s"})
        except Exception as e:  # keep the headline alive
            rows.append({"metric": "lenet_mnist_imperative", "error": str(e)})
        try:
            if over_budget():
                raise TimeoutError("bench budget exhausted")
            bert_sps = bench_bert_finetune(
                platform, iters, warmup)
            rows.append({
                "metric": "bert_base_sq384_bf16_finetune_samples_per_sec",
                "value": round(bert_sps, 2), "unit": "samples/s"})
        except Exception as e:
            rows.append({"metric": "bert_base_finetune", "error": str(e)})
        try:
            if over_budget():
                raise TimeoutError("bench budget exhausted")
            agreement = bench_int8_agreement(platform)
            rows.append({
                "metric": "int8_resnet18_top1_agreement_vs_fp32",
                "value": round(agreement, 4), "unit": "ratio",
                "note": "reference accuracy delta: 76.04 int8 vs 76.36 "
                        "fp32 top-1 = 99.6% relative "
                        "(example/quantization/README.md:113-121)"})
        except Exception as e:
            rows.append({"metric": "int8_agreement", "error": str(e)})

    # fused-update dispatch latency runs on every platform (no model
    # compile — the row times the optimizer dispatch path itself, which
    # exists on CPU too); >3% RISE trips the regression gate above
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        upd_ms = bench_trainer_update_ms(platform)
        rows.append({
            "metric": "trainer_update_ms",
            "value": round(upd_ms, 3), "unit": "ms",
            "note": "mean of 50 fused Trainer.update steps over a "
                    "ResNet-50-shaped param set (161 tensors, SGD "
                    "momentum, one donated dispatch per step)"})
    except Exception as e:
        rows.append({"metric": "trainer_update_ms", "error": str(e)})

    # whole-step vs phased A/B runs on every platform (on CPU a small
    # Dense stack keeps it cheap); _ms rows → lower-is-better gate
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        ws_iters = iters
        whole_ms, phased_ms, ws_img_s = bench_whole_step(
            platform, ws_iters, warmup)
        ab_note = ("gluon.TrainStep one-dispatch step vs legacy "
                   "record/backward/Trainer.step on the same "
                   "model+optimizer (docs/performance.md)")
        rows.append({
            "metric": "train_step_ms_wholestep",
            "value": round(whole_ms, 3), "unit": "ms", "note": ab_note})
        rows.append({
            "metric": "train_step_ms_phased",
            "value": round(phased_ms, 3), "unit": "ms", "note": ab_note})
        rows.append({
            "metric": "train_img_s_wholestep",
            "value": round(ws_img_s, 2), "unit": "img/s",
            "note": ab_note})
    except Exception as e:
        rows.append({"metric": "train_step_wholestep_ab", "error": str(e)})

    # observability overhead: numerics step-mode A/B + flight-recorder
    # hot-path cost; both _ms rows → lower-is-better gate, and the
    # numerics note carries the vs-off ratio (acceptance bar: <=3%)
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        nm_iters = iters
        nm_ms, off_ms = bench_numerics_overhead(platform, nm_iters, warmup)
        rows.append({
            "metric": "train_step_ms_numerics",
            "value": round(nm_ms, 3), "unit": "ms",
            "note": f"whole-step latency with MXTPU_NUMERICS=step "
                    f"(fused is-finite AND-reduce + async callback); "
                    f"vs off: {nm_ms / off_ms:.4f}x "
                    f"(off={off_ms:.3f}ms; docs/observability.md)"})
    except Exception as e:
        rows.append({"metric": "train_step_ms_numerics", "error": str(e)})

    # bandwidth kernels: whole-step A/B (MXTPU_KERNELS=auto vs 0) +
    # per-kernel microbenches; all _ms rows → lower-is-better gate
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        kn_iters = iters
        kn_ms, koff_ms = bench_kernels_overhead(platform, kn_iters,
                                                warmup)
        rows.append({
            "metric": "train_step_ms_kernels",
            "value": round(kn_ms, 3), "unit": "ms",
            "note": f"whole-step latency with MXTPU_KERNELS=auto "
                    f"(Pallas BN + optimizer-ladder kernels); vs "
                    f"MXTPU_KERNELS=0: {kn_ms / koff_ms:.4f}x "
                    f"(off={koff_ms:.3f}ms; docs/kernels.md)"})
    except Exception as e:
        rows.append({"metric": "train_step_ms_kernels", "error": str(e)})

    # layout pass: whole-step A/B (MXTPU_LAYOUT=auto vs off) on an NCHW
    # conv stack; the _ms row rides the lower-is-better gate and the
    # img/s row records the auto-side throughput (docs/layout.md)
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        ly_iters = iters
        ly_ms, ly_off_ms, ly_img_s = bench_layout_overhead(
            platform, ly_iters, warmup)
        ly_note = (f"whole-step latency with MXTPU_LAYOUT=auto "
                   f"(NHWC propagation + persistent HWIO weights); vs "
                   f"off: {ly_ms / ly_off_ms:.4f}x "
                   f"(off={ly_off_ms:.3f}ms; docs/layout.md)")
        rows.append({
            "metric": "train_step_ms_layout",
            "value": round(ly_ms, 3), "unit": "ms", "note": ly_note})
        rows.append({
            "metric": "train_img_s_nhwc_auto",
            "value": round(ly_img_s, 2), "unit": "img/s",
            "note": ly_note})
    except Exception as e:
        rows.append({"metric": "train_step_ms_layout", "error": str(e)})

    # hybrid parallelism: dp8 whole-step throughput + the one-time
    # ShardingPlan placement cost; img/s rides the higher-is-better
    # gate, the _ms row the lower-is-better gate (docs/sharding.md)
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        sh_iters = iters
        sh_img_s, sh_apply_ms = bench_sharding(platform, sh_iters, warmup)
        rows.append({
            "metric": "train_img_s_dp8",
            "value": round(sh_img_s, 2), "unit": "img/s",
            "note": "donated whole-step training over "
                    "Trainer(kvstore='tpu_dist', mesh=(('dp', -1),)) on "
                    "an 8-way data-parallel mesh (CPU: forced virtual "
                    "devices in a subprocess; docs/sharding.md)"})
        rows.append({
            "metric": "sharding_apply_ms",
            "value": round(sh_apply_ms, 3), "unit": "ms",
            "note": "one-time ShardingPlan.apply cost: NamedSharding "
                    "device_put of params+grads onto the dp8 mesh"})
    except Exception as e:
        rows.append({"metric": "train_img_s_dp8", "error": str(e)})

    # hybrid dp4 x tp2 whole-step + ZeRO optimizer memory: img/s rides
    # the higher-is-better gate, the _mb row the lower-is-better gate
    # (ISSUE 19; acceptance: >=3x reduction at fsdp=4 vs replicated)
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        hy_iters = iters
        hy = bench_hybrid(platform, hy_iters, warmup)
        rows.append({
            "metric": "train_img_s_tp2dp4",
            "value": round(hy["img_s"], 2), "unit": "img/s",
            "note": "donated whole-step GSPMD training on the SpecLayout "
                    "hybrid plan ShardingPlan.from_layout('dp=4,tp=2') "
                    "(CPU: forced virtual devices in a subprocess; "
                    "docs/sharding.md)"})
        ratio = hy["opt_state_mb_repl"] / max(hy["opt_state_mb"], 1e-9)
        rows.append({
            "metric": "opt_state_mb_per_dev",
            "value": round(hy["opt_state_mb"], 4), "unit": "MB",
            "note": f"per-device optimizer state under the ZeRO fsdp=4 "
                    f"plan (replicated: "
                    f"{round(hy['opt_state_mb_repl'], 4)} MB -> "
                    f"{ratio:.2f}x reduction; MXTPU_ZERO, "
                    f"docs/sharding.md)"})
    except Exception as e:
        rows.append({"metric": "train_img_s_tp2dp4", "error": str(e)})
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        micro = bench_kernel_micro_ms(platform)
        for kname, ms in micro.items():
            rows.append({
                "metric": f"kernel_{kname}_ms",
                "value": round(ms, 4), "unit": "ms",
                "note": "per-call microbench through the dispatching "
                        "entry point (kernel on TPU, XLA fallback "
                        "elsewhere; docs/kernels.md)"})
    except Exception as e:
        rows.append({"metric": "kernel_micro_ms", "error": str(e)})
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        fr_ms = bench_flightrec_record_ms()
        rows.append({
            "metric": "flightrec_record_ms",
            "value": round(fr_ms, 3), "unit": "ms",
            "note": "wall ms per 1000 flight.record() calls into a full "
                    "ring (steady state; docs/observability.md)"})
    except Exception as e:
        rows.append({"metric": "flightrec_record_ms", "error": str(e)})

    # live ops server: whole-step A/B (server + 10 Hz scraper vs no
    # server) + one-scrape cost; both _ms rows → lower-is-better gate
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        od_iters = iters
        od_ms, od_off_ms, od_scrape_ms = bench_opsd_overhead(
            platform, od_iters, warmup)
        rows.append({
            "metric": "train_step_ms_opsd",
            "value": round(od_ms, 3), "unit": "ms",
            "note": f"whole-step latency with the ops server up + a "
                    f"10 Hz /metrics scraper; vs no server: "
                    f"{od_ms / od_off_ms:.4f}x (off={od_off_ms:.3f}ms; "
                    f"docs/observability.md)"})
        rows.append({
            "metric": "opsd_scrape_ms",
            "value": round(od_scrape_ms, 3), "unit": "ms",
            "note": "one GET /metrics round-trip (serialize the full "
                    "registry to Prometheus text) on a warm registry"})
    except Exception as e:
        rows.append({"metric": "train_step_ms_opsd", "error": str(e)})

    # serving-engine QPS runs on every platform (cheap MLP — the row
    # measures the batching/dispatch path, which exists on CPU too)
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        qps = bench_serving_qps(platform)
        rows.append({
            "metric": "inference_qps",
            "value": round(qps, 2), "unit": "req/s",
            "note": "serving.InferenceEngine round-trip: 8 client "
                    "threads, dynamic batching through warmed buckets "
                    "(docs/serving.md)"})
    except Exception as e:
        rows.append({"metric": "inference_qps", "error": str(e)})

    # request-tracing A/B: the same closed loop with 0.1 head sampling
    # vs tracing off — the reqtrace acceptance bar is <3% qps regression
    # when sampled (higher-is-better gate catches a bleed here)
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        qps_off = bench_serving_qps(platform, trace_sample=0.0)
        qps_on = bench_serving_qps(platform, trace_sample=0.1)
        rows.append({
            "metric": "serve_qps_traced",
            "value": round(qps_on, 2), "unit": "req/s",
            "note": f"inference_qps with MXTPU_TRACE_SAMPLE=0.1 request "
                    f"tracing; vs untraced: {qps_on / qps_off:.4f}x "
                    f"(off={qps_off:.2f} req/s; docs/observability.md)"})
    except Exception as e:
        rows.append({"metric": "serve_qps_traced", "error": str(e)})

    # KV-cache decode runs on every platform (tiny model — the row
    # measures the paged-cache stepping path, not model FLOPs);
    # decode_tok_s → higher-is-better, decode_ttft_ms → lower-is-better
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        tok_s, ttft_ms = bench_decode(platform)
        decode_note = ("decode.DecodeEngine: 16 streamed sequences, "
                       "4 KV slots, continuous join/retire churn, zero "
                       "recompiles after warmup enforced "
                       "(docs/decode.md)")
        rows.append({
            "metric": "decode_tok_s",
            "value": round(tok_s, 2), "unit": "tok/s",
            "note": decode_note})
        rows.append({
            "metric": "decode_ttft_ms",
            "value": round(ttft_ms, 3), "unit": "ms",
            "note": "median time-to-first-token (queue + prefill + "
                    "first sample) in the same run; " + decode_note})
    except Exception as e:
        rows.append({"metric": "decode_tok_s", "error": str(e)})

    # checkpoint commit latency runs on every platform (host-side work:
    # capture + npz + fsync + rename); _ms suffix → lower-is-better gate
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        ck_ms = bench_ckpt_save_ms(platform)
        rows.append({
            "metric": "ckpt_save_ms",
            "value": round(ck_ms, 3), "unit": "ms",
            "note": "mean of 3 committed CheckpointManager saves of "
                    "ResNet-50-sized state (161 tensors + momentum, "
                    "async engine path, save+flush through fsync'd "
                    "rename; docs/checkpointing.md)"})
    except Exception as e:
        rows.append({"metric": "ckpt_save_ms", "error": str(e)})

    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        rs_ms = bench_reshard_restore_ms(platform)
        rows.append({
            "metric": "reshard_restore_ms",
            "value": round(rs_ms, 3), "unit": "ms",
            "note": "mean of 3 mesh-migrating restores (dp=4 checkpoint "
                    "onto a dp=2 trainer, allow_reshard=True: manifest "
                    "read + plan judgment + re-placement; "
                    "docs/elasticity.md)"})
    except Exception as e:
        rows.append({"metric": "reshard_restore_ms", "error": str(e)})

    # graph-pass pipeline build latency + peak program footprint run on
    # every platform (cheap MLP / registry read); both lower-is-better
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        pc_ms = bench_passes_compile_ms(platform)
        rows.append({
            "metric": "compile_ms_passes",
            "value": round(pc_ms, 3), "unit": "ms",
            "note": "first-call build of a small MLP through the "
                    "graph-pass pipeline: trace + AMP rewrite + dedup "
                    "hashing + XLA compile (docs/passes.md)"})
    except Exception as e:
        rows.append({"metric": "compile_ms_passes", "error": str(e)})
    try:
        if over_budget():
            raise TimeoutError("bench budget exhausted")
        hbm_mb = bench_peak_hbm_mb(platform)
        rows.append({
            "metric": "peak_hbm_mb",
            "value": round(hbm_mb, 3), "unit": "MB",
            "note": "largest program footprint in this run's compile "
                    "registry (liveness peak when available, else XLA "
                    "memory_analysis; the remat pass bends this row — "
                    "docs/passes.md)"})
    except Exception as e:
        rows.append({"metric": "peak_hbm_mb", "error": str(e)})

    result_extra = {}
    try:
        # compile counts / transfer+collective bytes / step metrics ride
        # along with the throughput numbers, so a BENCH_*.json regression
        # can be read against what the runtime actually did
        # (docs/telemetry.md)
        from mxnet_tpu import telemetry

        result_extra["telemetry"] = telemetry.dump()
    except Exception as e:  # never let observability sink the headline
        result_extra["telemetry"] = {"error": str(e)}
    result = {
        **result_extra,
        "platform": platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "backend": jax.default_backend(),
        "metric": f"resnet50_train_bf16_b{batch}_{layout.lower()}"
                  "_imgs_per_sec_per_chip",
        "value": round(train_img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(train_img_s / BASELINE_TRAIN_IMG_S, 4),
        "baseline": "V100 fp32 b=128 training 363.69 img/s "
                    "(reference perf.md:243-253; best published batch — "
                    "throughput-vs-throughput comparison)",
        "rows": rows,
    }
    try:
        regressions = _check_regressions(result)
    except Exception as e:  # the comparison must never sink the headline
        regressions = [{"error": str(e)}]
    if regressions:
        result["regressions"] = regressions
    try:
        # with MXTPU_MEASURE on, the bench programs were measured into
        # the CostDB — surface the summary + drift verdicts alongside
        # the headline numbers (docs/performance.md measured-vs-modeled)
        from mxnet_tpu.observability import costdb, measure

        if measure.enabled():
            measure.sweep()
            costdb.db().save()
            rep = costdb.drift_report()
            result["costdb"] = dict(costdb.db().summary(),
                                    tripped=[r["program"]
                                             for r in rep["tripped"]])
    except Exception:
        pass
    print(json.dumps(result))


if __name__ == "__main__":
    main()
