"""Driver: a training cell through gluon.Trainer + gluon.TrainStep.

Set-up builds ONE TrainStep with its state, drives it through its first
three steps from the seed (on three different batches, through the very
call the window uses) and hands that same object to the window.  The
plain reference follows those three steps after the window has closed
and the program's state has been freed.
"""
import gc
import time

import numpy as onp

import weights as wmod
from compare import train_numbers
from reference import optim, train_ref

REF_STEPS = 3
FETCH_EVERY = 10        # a logging loop reads the loss every 10th step
TRACE_STEPS = 10


def _unwrap(tree, jax, NDArray):
    return jax.tree_util.tree_map(
        lambda a: a._data if isinstance(a, NDArray) else a, tree,
        is_leaf=lambda a: isinstance(a, NDArray))


def program_state(net, trainer, opt, jax, NDArray):
    """{gluon name: (float32 weight or master, optimizer state)} of every
    trained leaf, read where the optimizer keeps them."""
    names = {id(p): n for n, p in net.collect_params().items()}
    out = {}
    for i, p in enumerate(trainer._params):
        if p.grad_req == "null":
            continue
        w = p.data()._data
        st = _unwrap(trainer._states[i], jax, NDArray)
        if opt.get("multi_precision") and w.dtype.name in ("bfloat16",
                                                           "float16"):
            master, inner = st
        else:
            master, inner = w, st
        out[names[id(p)]] = (master, inner)
    return out


def run(h):
    jax, mx = h.jax, h.mx
    import jax.numpy as jnp

    from mxnet_tpu import gluon
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.telemetry import instruments as ti

    cfg, traffic, ref = h.cfg, h.traffic, h.reference
    opt = cfg["optimizer"]
    limits = cfg["limits"]["train_step"]
    b, pool = traffic["batch"], traffic["pool"]
    if pool < REF_STEPS:
        raise ValueError(f"pool {pool} < {REF_STEPS}: the first steps need "
                         "batches that all differ")
    ctx = mx.tpu(0)

    with h.span("make_weights"):
        weights = wmod.make_weights(ref.param_specs(cfg), h.seed,
                                    cfg["dtype"])
        batches = wmod.make_batches(ref.input_specs(cfg, b), h.seed, pool)
    with h.span("build"):
        net = h.model.build(mx, cfg, weights, ctx)
        loss_fn, n_data = h.model.loss(mx, cfg)
        trainer = gluon.Trainer(
            net.collect_params(), opt["name"],
            {k: v for k, v in opt.items() if k != "name"},
            kvstore="tpu_dist")
        step = gluon.TrainStep(net, loss_fn, trainer, n_data=n_data)
    feed = [tuple(NDArray(a) for a in bt) for bt in batches]
    w0 = {n: w for n, w in weights.items() if ref.trainable(n)}

    @jax.jit
    def grad_norms(w0, inner):
        return train_ref.leaf_norms(
            {n: optim.first_gradient(opt, w0[n], inner[n]) for n in inner})

    @jax.jit
    def change_norms(w0, now):
        return train_ref.leaf_norms(
            {n: now[n].astype(jnp.float32) - w0[n] for n in now})

    def traces():
        return sum(c.value for _, c in ti.jit_trace_total.series())

    # -- the first steps: through the window's own call and feed ---------
    prog_losses, prog_gn = [], None
    for k in range(REF_STEPS):
        with h.span("first_call" if k == 0 else "first_steps",
                    compile=(k == 0)):
            loss = step(*feed[k])
            prog_losses.append(float(onp.mean(
                onp.asarray(loss._data).astype(onp.float32))))
        if k == 0:
            with h.span("grad_norms", compile=True):
                st = program_state(net, trainer, opt, jax, NDArray)
                prog_gn = grad_norms(w0, {n: s[1] for n, s in st.items()})
                prog_gn = {n: float(v) for n, v in prog_gn.items()}
    with h.span("change_norms", compile=True):
        st = program_state(net, trainer, opt, jax, NDArray)
        prog_dw = change_norms(w0, {n: s[0] for n, s in st.items()})
        prog_dw = {n: float(v) for n, v in prog_dw.items()}
    if step.last_path != "whole_step":
        raise RuntimeError(f"TrainStep ran {step.last_path}: "
                           f"{step.ineligible_reason()}")
    # every batch of the pool once more, with the fetch the window makes
    with h.span("warm_pool"):
        for k in range(pool):
            loss = step(*feed[k])
        onp.asarray(loss._data)
    traces0, step_traces0 = traces(), step.jit_trace_count()

    # -- the window ------------------------------------------------------
    pauses = []         # the collector's pauses in the window: [generation, s]

    def on_gc(phase, info):
        if phase == "start":
            pauses.append([info["generation"], time.perf_counter()])
        else:
            pauses[-1][1] = time.perf_counter() - pauses[-1][1]

    gc.callbacks.append(on_gc)
    setup_s = h.window_opens()
    dispatch, n, tracing, want_trace, traced_steps = [], 0, False, h.trace, 0
    t0 = time.perf_counter()
    deadline = t0 + h.seconds
    while True:
        if want_trace and n == FETCH_EVERY // 2:
            jax.block_until_ready(loss._data)
            h.trace_start()
            tracing, want_trace = n, False
        ts = time.perf_counter()
        with h.annotate("enqueue_step"):
            loss = step(*feed[n % pool])
        dispatch.append(time.perf_counter() - ts)
        n += 1
        if n % FETCH_EVERY == 0:
            with h.annotate("fetch_loss"):
                last = onp.asarray(loss._data)
            if not onp.all(onp.isfinite(last.astype(onp.float32))):
                raise FloatingPointError(f"loss not finite at step {n}")
        if tracing is not False and n == tracing + TRACE_STEPS:
            with h.annotate("wait_device"):
                jax.block_until_ready(loss._data)
            h.trace_stop()
            tracing, traced_steps = False, TRACE_STEPS
        if time.perf_counter() >= deadline:
            break
    jax.block_until_ready(
        [loss._data] + [p.data()._data
                        for p in net.collect_params().values()])
    window_s = time.perf_counter() - t0
    gc.callbacks.remove(on_gc)
    h.note(gc_pauses_ms=[[g, d * 1e3] for g, d in pauses if d >= 5e-3],
           slowest_dispatch_ms=sorted(
               ([k, d * 1e3] for k, d in enumerate(dispatch)),
               key=lambda kd: -kd[1])[:3])
    if tracing is not False:       # the window closed inside the slice
        h.trace_stop()
        traced_steps = n - tracing
    retraces = (traces() - traces0) + (step.jit_trace_count()
                                       - step_traces0)
    peak = h.memory_peak()

    # -- free the program, then the reference ----------------------------
    del step, trainer, net, feed, st, loss
    gc.collect()
    checks = h.checks
    t_ref = time.perf_counter()
    ref_losses, ref_gn, ref_dw = train_ref.train_steps(
        ref, cfg, weights, batches, REF_STEPS)
    weight_leaves = [n for n, w in w0.items() if w.ndim >= 2]
    for name, value, note in train_numbers(
            (prog_losses, prog_gn, prog_dw), (ref_losses, ref_gn, ref_dw),
            weight_leaves):
        base, _, k = name.partition(".step")
        limit = limits[base][int(k) - 1] if k else limits[name]
        checks.add(name, value, limit, note)
    checks.add("retraces_in_window", retraces, 0)
    h.dump("leaves", {"losses": [prog_losses, ref_losses],
                      "grad_norm": [prog_gn, ref_gn],
                      "dw_norm": [prog_dw, ref_dw]})
    h.note(reference_s=time.perf_counter() - t_ref)

    rate = n * b / window_s
    return {
        "setup_s": setup_s,
        "attempted": n, "failed": 0,
        "end_to_end": {"train_samples_s": rate},
        "memory_peak_bytes": peak,
        "run": {"dispatch_s": dispatch, "steps": n, "batch": b,
                "traced_steps": traced_steps, "window_s": window_s},
    }
