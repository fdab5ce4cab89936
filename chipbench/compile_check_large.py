#!/usr/bin/env python3
"""Scratch: `compile_check.py` for a cell of the ``train_step_large``
driver — the whole step exactly as gluon.TrainStep builds it, and the two
programs of `reference/train_ref_large.py` (loss + gradients, one leaf's
update), compiled for a DESCRIBED v5e at the real size without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/compile_check_large.py --workload <cell>

Nothing here is a measurement.  ``--hlo <file>`` keeps the whole step's
optimized HLO text.
"""
import argparse
import importlib
import json
import os
import time

from compile_check import HERE, _Captured, _report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--skip-program", action="store_true")
    ap.add_argument("--skip-reference", action="store_true")
    ap.add_argument("--hlo", default=None)
    args = ap.parse_args()

    import mxnet_tpu as mx
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
            if hasattr(x, "shape") else x, tree)

    with open(os.path.join(HERE, "workloads", args.workload + ".json")) as f:
        wl = json.load(f)
    with open(os.path.join(HERE, "configs", wl["config"] + ".json")) as f:
        cfg = json.load(f)
    ref = importlib.import_module("reference." + cfg["builder"])
    model = importlib.import_module("models." + cfg["builder"])
    specs = ref.param_specs(cfg)
    batch = tuple(jnp.zeros(s, jnp.int32 if k != "uniform" else jnp.float32)
                  for s, k, *_ in ref.input_specs(
                      cfg, wl["traffic_params"]["batch"]))
    if not args.skip_program:
        from mxnet_tpu import gluon
        from mxnet_tpu.ndarray.ndarray import NDArray
        from mxnet_tpu.ops import pallas_attention

        # off a TPU flash_attention takes its jnp twin; this compile is for
        # the chip, so the kernel is asked for by name
        kernel = pallas_attention.flash_attention
        pallas_attention.flash_attention = lambda *a, **kw: kernel(
            *a, **{**kw, "interpret": False})

        opt = cfg["optimizer"]
        weights = {n: jnp.zeros(s, jnp.float32) for n, s, *_ in specs}
        net = model.build(mx, cfg, weights, mx.tpu(0))
        del weights
        loss_fn, n_data = model.loss(mx, cfg)
        trainer = gluon.Trainer(
            net.collect_params(), opt["name"],
            {k: v for k, v in opt.items() if k != "name"},
            kvstore="tpu_dist")
        step = gluon.TrainStep(net, loss_fn, trainer, n_data=n_data)
        jitted = step._jitted

        def intercept(donate):
            fn = jitted(donate)

            def lower_only(*a):
                t = time.perf_counter()
                raise _Captured(fn.lower(*described(a)).compile(),
                                time.perf_counter() - t)
            return lower_only

        step._jitted = intercept
        try:
            step(*[NDArray(a) for a in batch])
        except _Captured as c:
            _report("program: gluon.TrainStep whole step", *c.args)
            if args.hlo:
                with open(args.hlo, "w") as f:
                    f.write(c.args[0].as_text())
        del net, trainer, step
    if not args.skip_reference:
        shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
                  for n, s, *_ in specs}
        train = {n: w for n, w in shapes.items() if ref.trainable(n)}
        frozen = {n: w for n, w in shapes.items() if n not in train}

        def ref_step(train, frozen, batch):
            def total(tr):
                return jnp.sum(ref.per_sample_loss(
                    cfg, {**tr, **frozen}, batch))
            return jax.value_and_grad(total)(train)

        t = time.perf_counter()
        compiled = jax.jit(ref_step).lower(
            train, frozen, described(batch)).compile()
        _report("reference: float32 loss + gradients", compiled,
                time.perf_counter() - t)


if __name__ == "__main__":
    main()
