"""Driver: `train_step`'s cell for a model whose state fills the chip.

The same ONE TrainStep, the same three first steps, the same window and
the same numbers compared (`Program`, `first_steps`, `window`, `result`
and `compare.train_numbers`, all as `train_step` has them, so that
``train_samples_s`` means what it means there).  What differs is what is
resident beside the program: `train_step.run` keeps the seeded float32
weights on the device through the window and `train_ref.train_steps`
holds weights and optimizer state twice.  Here the seeded weights are
dropped once the net holds them (the first steps compare against a copy
in the configuration's own type, which holds a rounded leaf exactly),
that copy is dropped before the window, and after the program is freed
the reference makes the weights again from the same seed
(`weights.weights_seed`: the configuration's one stated draw, else
``--seed``; `reference/train_ref_large.py`).

A traced run also notes, for the per-layer readers: the rows that the
plain reference's own routing sends to the held experts on the pool's
batches at the seeded weights (``held_rows`` of the reference, where it
has one), and the program's expert-load counters of the last step.
"""
import gc
import itertools
import time

import numpy as onp

import weights as wmod
from compare import train_numbers
from drivers.train_step import (REF_STEPS, Program, first_steps,
                                reference_batches, result, window)
from reference import train_ref_large

__all__ = ["run", "reference_batches"]


def program_moe_load():
    """{layer: (rows routed here, load max over mean)} of the last step by
    the program's own counters; None on a program without them."""
    from mxnet_tpu.telemetry import instruments as ti

    flush = getattr(ti, "flush_moe_load", None)
    return flush() if flush is not None else None


def compare(h, make_weights, batches, prog_numbers, retraces):
    """`train_step.compare`, the reference through
    `train_ref_large.train_steps`."""
    ref = h.reference
    limits = h.cfg["limits"]["train_step"]
    t_ref = time.perf_counter()
    ref_numbers = train_ref_large.train_steps(ref, h.cfg, make_weights,
                                              batches, REF_STEPS)
    weight_leaves = [n for n, shape, *_ in ref.param_specs(h.cfg)
                     if ref.trainable(n) and len(shape) >= 2]
    for name, value, note in train_numbers(prog_numbers, ref_numbers,
                                           weight_leaves):
        base, _, k = name.partition(".step")
        limit = limits[base][int(k) - 1] if k else limits[name]
        h.checks.add(name, value, limit, note)
    h.checks.add("retraces_in_window", retraces, 0)
    h.dump("leaves", {k: [p, r] for k, p, r in zip(
        ("losses", "grad_norm", "dw_norm"), prog_numbers, ref_numbers)})
    h.note(reference_s=time.perf_counter() - t_ref)


def reference_held_rows(h, make_weights, batches):
    """Rows per step, summed over the layers, that the reference's routing
    gives the held experts: the mean over the pool's batches."""
    held_rows = getattr(h.reference, "held_rows", None)
    if held_rows is None:
        return None
    weights = make_weights()
    count = h.jax.jit(lambda w, b: held_rows(h.cfg, w, b))
    rows = [onp.asarray(count(weights, b)) for b in batches]
    h.note(reference_held_rows=[r.tolist() for r in rows])
    return float(onp.mean([r.sum() for r in rows]))


def run(h):
    cfg, traffic, ref = h.cfg, h.traffic, h.reference
    pool = traffic["pool"]
    if pool < REF_STEPS:
        raise ValueError(f"pool {pool} < {REF_STEPS}: the first steps need "
                         "batches that all differ")
    specs = ref.param_specs(cfg)
    w_seed = wmod.weights_seed(cfg, h.seed)
    h.note(weights_seed=w_seed)
    if "pool_seed" in traffic:
        h.note(pool_seed=traffic["pool_seed"],
               pool_order=wmod.pool_order(h.seed, pool, REF_STEPS))

    def make_weights():
        return wmod.make_weights(specs, w_seed, cfg["dtype"])

    with h.span("make_weights"):
        weights = make_weights()
        batches = reference_batches(cfg, traffic, h.seed, pool, ref)
    with h.span("build"):
        prog = Program(h, weights)
    # what the first steps are compared against, in the type that holds a
    # rounded leaf exactly: half the bytes beside the program's state
    low = {n for n, _shape, _kind, _arg, rounded in specs if rounded}
    w0 = {n: w.astype(cfg["dtype"]) if n in low else w
          for n, w in weights.items() if ref.trainable(n)}
    del weights
    feed = [tuple(prog.NDArray(a) for a in bt) for bt in batches]
    prog_numbers = first_steps(h, prog, w0, feed)
    del w0
    # every batch of the pool once more, with the fetch the window makes
    with h.span("warm_pool"):
        for k in range(pool):
            loss = prog.step(*feed[k])
        onp.asarray(loss._data)

    setup_s, run_, peak = window(h, prog, itertools.cycle(feed).__next__,
                                 loss)
    if h.trace:
        run_["moe_load"] = program_moe_load()
        h.note(moe_load=run_["moe_load"])

    # -- free the program, then the reference ----------------------------
    prog.free()
    del feed, loss
    gc.collect()
    compare(h, make_weights, batches, prog_numbers, run_["retraces"])
    if h.trace:
        run_["reference_held_rows"] = reference_held_rows(h, make_weights,
                                                          batches)
    return result(traffic["batch"], setup_s, run_, peak)
