"""Driver: ``train_step``'s cell with its batches coming from
gluon.data.DataLoader -- the input path of a Gluon training script.

The model, the optimizer, the ONE TrainStep, the first three steps, the
window and the reference are ``train_step``'s.  What differs is the
feed: a ``Dataset`` over a seeded pool of decoded uint8 images
(``imagedata.py``) whose item is flipped, cast and normalised as the
training script does it, behind ``DataLoader(dataset, **loader)`` with
the workload file's arguments and every other at its default.  The loop
is ``for data, label in loader: loss = step(data, label)``.

Beyond ``train_step``'s numbers ``correct`` holds the loader to its
batches: the three the reference follows and the window's last equal a
NumPy recomputation bit for bit, and every batch drawn carries the
labels of its place in the sampler's seeded order, none twice and none
left out.
"""
import gc
import multiprocessing

import numpy as onp
from mxnet_tpu.diagnostics import spans
from mxnet_tpu.gluon.data import DataLoader, Dataset

import imagedata
import weights as wmod
from drivers import train_step as ts


class ImagePool(Dataset):
    """Item ``i`` is pool image ``i mod pool``, transformed, with its
    label: an epoch of ``dataset_length`` over a decoded-image cache."""

    def __init__(self, pool, seed, tp):
        self.images, self.labels = pool
        self.seed, self.flip_p = seed, tp["flip_p"]
        self.length = tp["dataset_length"]
        self.mean = onp.asarray(tp["mean"], onp.float32)
        self.std = onp.asarray(tp["std"], onp.float32)

    def __len__(self):
        return self.length

    def __getitem__(self, i):
        at = i % len(self.images)
        flip = imagedata.flipped(self.seed, i, self.flip_p)
        return (imagedata.transform(self.images[at], flip, self.mean,
                                    self.std), self.labels[at])


def make_pool(cfg, tp, seed):
    return imagedata.make_pool(seed, tp["pool_images"], cfg["image"],
                               cfg["classes"])


def reference_batches(cfg, traffic, seed, n, ref=None):
    """The ``n`` batches the first steps of this driver's cell see,
    recomputed without a loader."""
    pool = make_pool(cfg, traffic, seed)
    order = imagedata.sampler_order(seed, traffic["dataset_length"])
    b = traffic["loader"]["batch_size"]
    return [imagedata.recompute_batch(
        pool, imagedata.batch_indices(order, k, b), seed, traffic)
        for k in range(n)]


def _host(batch):
    return tuple(onp.asarray(a._data) for a in batch)


def _differing(a, b):
    """Elements of ``a`` whose bits differ from ``b``'s (all of them
    where shape or type do)."""
    if a.shape != b.shape or a.dtype != b.dtype:
        return a.size
    return int(onp.count_nonzero(
        a.view(onp.uint32) != b.view(onp.uint32)))


def check_feed(h, pool, host_batches, labels_drawn, steps):
    """``correct`` (b) and (d).  ``host_batches``: {place in the epoch:
    (data, label)} as the loader yielded them; ``labels_drawn``: the
    label vector of EVERY batch drawn, in the order drawn; ``steps``: the
    steps the run made, set-up's and the window's."""
    tp = h.traffic
    b = tp["loader"]["batch_size"]
    order = imagedata.sampler_order(h.seed, tp["dataset_length"])
    bits = 0
    for k, got in host_batches.items():
        want = imagedata.recompute_batch(
            pool, imagedata.batch_indices(order, k, b), h.seed, tp)
        bits += sum(_differing(g, w) for g, w in zip(got, want))
    h.checks.add("batch_bits_differing", bits, 0,
                 f"batches {sorted(host_batches)} of the epoch against "
                 "their recomputation")
    _images, labels = pool
    wrong = sum(
        not onp.array_equal(got, labels[
            imagedata.batch_indices(order, k, b) % len(labels)])
        for k, got in enumerate(labels_drawn))
    h.checks.add("batches_out_of_order", wrong, 0,
                 f"{len(labels_drawn)} batches drawn, by their labels")
    h.checks.add("samples_not_consumed",
                 abs(sum(len(l) for l in labels_drawn) - steps * b), 0,
                 f"samples drawn against {steps} steps of {b}")


def run(h):
    cfg, tp, ref = h.cfg, h.traffic, h.reference
    w_seed = wmod.weights_seed(cfg, h.seed)
    h.note(weights_seed=w_seed)
    with h.span("make_weights"):
        weights = wmod.make_weights(ref.param_specs(cfg), w_seed,
                                    cfg["dtype"])
    with h.span("make_pool"):
        pool = make_pool(cfg, tp, h.seed)
        loader = DataLoader(ImagePool(pool, h.seed, tp), **tp["loader"])
    with h.span("build"):
        prog = ts.Program(h, weights)
    w0 = {n: w for n, w in weights.items() if ref.trainable(n)}

    drawn = []          # every batch's labels, on the device, as drawn
    newest = []         # the batch drawn last, whole

    def next_batch():
        with h.annotate("next_batch"):
            batch = next(it)
        drawn.append(batch[1])
        newest[:] = [batch]
        return batch

    # the sampler draws its order from NumPy's global generator when the
    # loader starts: seed it as a script's mx.random.seed does
    onp.random.seed(imagedata.numpy_seed(h.seed))
    it = iter(loader)
    with h.span("loader_start"):        # order drawn, workers started
        feed = [next_batch()]
    with h.span("first_batches"):
        feed += [next_batch() for _ in range(ts.REF_STEPS - 1)]
        host = {k: _host(bt) for k, bt in enumerate(feed)}
    prog_numbers = ts.first_steps(h, prog, w0, feed)
    del feed
    # the loader filled its prefetch queue while the step compiled: draw
    # that backlog off, so that the window sees the rate the loader
    # sustains over an epoch and not what a pause had stored up
    with h.span("warm_loader"):
        for _ in range(tp["warm_steps"]):
            loss = prog.step(*next_batch())
        onp.asarray(loss._data)

    setup_s, run_, peak = ts.window(h, prog, next_batch, loss)

    # -- stop the loader, free the program, then the checks --------------
    with h.span("loader_stop"):
        host[len(drawn) - 1] = _host(newest.pop())  # the window's last batch
        it.close()
        del it, loader
        gc.collect()
        left = multiprocessing.active_children()
        for p in left:
            p.terminate()
        for p in left:
            p.join()
    # the window's waits, and when each batch arrived, from the first
    # wait's start: the rate over any shorter window can be read off
    waits = [r for r in spans.records()
             if r["name"] == "dataloader_next"][-run_["steps"]:]
    h.note(loader_workers_left=len(left),
           data_wait_ms=[round(r["dur"] * 1e3, 1) for r in waits],
           batch_at_s=[round(r["t0"] + r["dur"] - waits[0]["t0"], 3)
                       for r in waits])
    labels_drawn = [onp.asarray(l._data) for l in drawn]
    prog.free()
    del loss, drawn
    gc.collect()
    check_feed(h, pool, host, labels_drawn,
               ts.REF_STEPS + tp["warm_steps"] + run_["steps"])
    ts.compare(h, weights, [host[k] for k in range(ts.REF_STEPS)],
               prog_numbers, run_["retraces"])
    return ts.result(tp["loader"]["batch_size"], setup_s, run_, peak)
