"""Driver: a training cell through gluon.Trainer + gluon.TrainStep.

Set-up builds ONE TrainStep with its state, drives it through its first
three steps from the seed (on three different batches, through the very
call the window uses) and hands that same object to the window.  The
plain reference follows those three steps after the window has closed
and the program's state has been freed.

``run`` feeds the step from a pool of resident batches.  The parts
(``Program``, ``first_steps``, ``window``, ``compare``) are what any
driver of a TrainStep is made of: ``train_loader`` feeds the same step
from gluon.data.DataLoader.
"""
import gc
import itertools
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


def reference_batches(cfg, traffic, seed, n, ref):
    """The first ``n`` batches a run of this driver's cell sees: drawn from
    ``--seed``; or, where the traffic states its pool (``pool_seed``), that
    pool's batches in the order ``--seed`` draws, the compared steps' own
    batches first."""
    specs = ref.input_specs(cfg, traffic["batch"])
    if "pool_seed" not in traffic:
        return wmod.make_batches(specs, seed, n)
    pool = wmod.make_batches(specs, traffic["pool_seed"], traffic["pool"])
    order = wmod.pool_order(seed, traffic["pool"], REF_STEPS)
    return [pool[k] for k in order[:n]]


class Program:
    """The system under test: net, trainer and the ONE TrainStep, built
    through the normal entry points on the seeded ``weights``."""

    def __init__(self, h, weights):
        from mxnet_tpu import gluon
        from mxnet_tpu.ndarray.ndarray import NDArray
        from mxnet_tpu.telemetry import instruments as ti

        self.h, self.NDArray, self._ti = h, NDArray, ti
        self.opt = opt = h.cfg["optimizer"]
        self.net = h.model.build(h.mx, h.cfg, weights, h.mx.tpu(0))
        loss_fn, n_data = h.model.loss(h.mx, h.cfg)
        self.trainer = gluon.Trainer(
            self.net.collect_params(), opt["name"],
            {k: v for k, v in opt.items() if k != "name"},
            kvstore="tpu_dist")
        self.step = gluon.TrainStep(self.net, loss_fn, self.trainer,
                                    n_data=n_data)

    def state(self):
        return program_state(self.net, self.trainer, self.opt, self.h.jax,
                             self.NDArray)

    def traces(self):
        """Programs traced so far, by the program's own counters."""
        return (sum(c.value for _, c in self._ti.jit_trace_total.series())
                + self.step.jit_trace_count())

    def params(self):
        return [p.data()._data for p in self.net.collect_params().values()]

    def free(self):
        self.net = self.trainer = self.step = None


def first_steps(h, prog, w0, feed):
    """The first ``REF_STEPS`` steps, through the window's own call, on
    ``feed[k]``: (mean loss of each, {leaf: norm of the first gradient as
    the optimizer got it}, {leaf: norm of the parameters' change})."""
    jax = h.jax
    import jax.numpy as jnp

    opt = prog.opt

    @jax.jit
    def grad_norms(w0, inner):
        return train_ref.leaf_norms(
            {n: optim.first_gradient(opt, w0[n], inner[n]) for n in inner})

    @jax.jit
    def change_norms(w0, now):
        return train_ref.leaf_norms(
            {n: now[n].astype(jnp.float32) - w0[n] for n in now})

    losses, gn = [], None
    for k in range(REF_STEPS):
        with h.span("first_call" if k == 0 else "first_steps",
                    compile=(k == 0)):
            loss = prog.step(*feed[k])
            losses.append(float(onp.mean(
                onp.asarray(loss._data).astype(onp.float32))))
        if k == 0:
            with h.span("grad_norms", compile=True):
                gn = grad_norms(w0, {n: s[1] for n, s in prog.state().items()})
                gn = {n: float(v) for n, v in gn.items()}
    with h.span("change_norms", compile=True):
        dw = change_norms(w0, {n: s[0] for n, s in prog.state().items()})
        dw = {n: float(v) for n, v in dw.items()}
    if prog.step.last_path != "whole_step":
        raise RuntimeError(f"TrainStep ran {prog.step.last_path}: "
                           f"{prog.step.ineligible_reason()}")
    return losses, gn, dw


def window(h, prog, next_batch, loss):
    """The measured window: ``step(*next_batch())`` back to back for
    ``h.seconds``, the loss fetched every ``FETCH_EVERY``-th step, closed
    by ``block_until_ready`` on loss and parameters.  ``loss`` is the
    warm-up's last.  Returns (``setup_s``, the run's record for the
    per-layer readers, peak device memory)."""
    jax, step = h.jax, prog.step
    pauses = []         # the collector's pauses in the window: [generation, s]

    def on_gc(phase, info):
        if phase == "start":
            pauses.append([info["generation"], time.perf_counter()])
        else:
            pauses[-1][1] = time.perf_counter() - pauses[-1][1]

    traces0 = prog.traces()
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
        batch = next_batch()
        ts = time.perf_counter()
        with h.annotate("enqueue_step"):
            loss = step(*batch)
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
    jax.block_until_ready([loss._data] + prog.params())
    window_s = time.perf_counter() - t0
    gc.callbacks.remove(on_gc)
    h.note(gc_pauses_ms=[[g, d * 1e3] for g, d in pauses if d >= 5e-3],
           slowest_dispatch_ms=sorted(
               ([k, d * 1e3] for k, d in enumerate(dispatch)),
               key=lambda kd: -kd[1])[:3])
    if tracing is not False:       # the window closed inside the slice
        h.trace_stop()
        traced_steps = n - tracing
    record = {"dispatch_s": dispatch, "steps": n,
              "traced_steps": traced_steps, "window_s": window_s,
              "retraces": prog.traces() - traces0}
    return setup_s, record, h.memory_peak()


def compare(h, weights, batches, prog_numbers, retraces):
    """The plain reference through the same first steps on ``batches``,
    and every number of the program beside it under the configuration's
    limits."""
    ref = h.reference
    limits = h.cfg["limits"]["train_step"]
    t_ref = time.perf_counter()
    ref_numbers = train_ref.train_steps(ref, h.cfg, weights, batches,
                                        REF_STEPS)
    weight_leaves = [n for n, w in weights.items()
                     if ref.trainable(n) and w.ndim >= 2]
    for name, value, note in train_numbers(prog_numbers, ref_numbers,
                                           weight_leaves):
        base, _, k = name.partition(".step")
        limit = limits[base][int(k) - 1] if k else limits[name]
        h.checks.add(name, value, limit, note)
    h.checks.add("retraces_in_window", retraces, 0)
    h.dump("leaves", {k: [p, r] for k, p, r in zip(
        ("losses", "grad_norm", "dw_norm"), prog_numbers, ref_numbers)})
    h.note(reference_s=time.perf_counter() - t_ref)


def result(batch, setup_s, run, peak):
    """What ``run.py`` takes from a driver; the rate is samples completed
    over the seconds of the whole window."""
    return {
        "setup_s": setup_s,
        "attempted": run["steps"], "failed": 0,
        "end_to_end": {"train_samples_s":
                       run["steps"] * batch / run["window_s"]},
        "memory_peak_bytes": peak,
        "run": dict(run, batch=batch),
    }


def run(h):
    cfg, traffic, ref = h.cfg, h.traffic, h.reference
    pool = traffic["pool"]
    if pool < REF_STEPS:
        raise ValueError(f"pool {pool} < {REF_STEPS}: the first steps need "
                         "batches that all differ")
    w_seed = wmod.weights_seed(cfg, h.seed)
    h.note(weights_seed=w_seed)
    if "pool_seed" in traffic:
        h.note(pool_seed=traffic["pool_seed"],
               pool_order=wmod.pool_order(h.seed, pool, REF_STEPS))
    with h.span("make_weights"):
        weights = wmod.make_weights(ref.param_specs(cfg), w_seed,
                                    cfg["dtype"])
        batches = reference_batches(cfg, traffic, h.seed, pool, ref)
    with h.span("build"):
        prog = Program(h, weights)
    feed = [tuple(prog.NDArray(a) for a in bt) for bt in batches]
    w0 = {n: w for n, w in weights.items() if ref.trainable(n)}
    prog_numbers = first_steps(h, prog, w0, feed)
    # every batch of the pool once more, with the fetch the window makes
    with h.span("warm_pool"):
        for k in range(pool):
            loss = prog.step(*feed[k])
        onp.asarray(loss._data)

    setup_s, run_, peak = window(h, prog, itertools.cycle(feed).__next__,
                                 loss)

    # -- free the program, then the reference ----------------------------
    prog.free()
    del feed, loss
    gc.collect()
    compare(h, weights, batches, prog_numbers, run_["retraces"])
    return result(traffic["batch"], setup_s, run_, peak)
