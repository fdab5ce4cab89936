#!/usr/bin/env python3
"""Scratch: compile a cell's programs for a DESCRIBED v5e at the real
size, without the chip, and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 chipbench/compile_check.py --workload <cell>

Run it before a cell's first chip call: what the chip's compiler refuses
(a program that does not fit 16 GB, a kernel it cannot lower) it refuses
here, at no chip time.  For a ``train_step`` cell it compiles the whole
step exactly as gluon.TrainStep builds it (the step is intercepted at
its jit, lowered against shapes placed on the described chip, and never
run) and the plain reference's step.  Nothing here is a measurement.
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE)):
    sys.path.insert(0, p)


class _Captured(Exception):
    pass


def _report(name, compiled, seconds):
    m = compiled.memory_analysis()
    gb = 1 / 2 ** 30
    print(json.dumps({
        "program": name, "compile_s": round(seconds, 1),
        "argument_gib": round(m.argument_size_in_bytes * gb, 3),
        "output_gib": round(m.output_size_in_bytes * gb, 3),
        "alias_gib": round(m.alias_size_in_bytes * gb, 3),
        "temp_gib": round(m.temp_size_in_bytes * gb, 3),
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--skip-program", action="store_true")
    ap.add_argument("--skip-reference", action="store_true")
    args = ap.parse_args()

    import importlib

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
    tp = wl["traffic_params"]
    specs = ref.param_specs(cfg)
    weights = {n: jnp.zeros(s, jnp.float32) for n, s, *_ in specs}

    if wl["driver"] not in ("train_step", "train_loader"):
        raise KeyError(f"no compile check for driver {wl['driver']!r}")
    b = tp.get("batch") or tp["loader"]["batch_size"]
    batch = tuple(jnp.zeros(s, jnp.int32 if k != "uniform"
                            else jnp.float32)
                  for s, k, *_ in ref.input_specs(cfg, b))
    if not args.skip_program:
        from mxnet_tpu import gluon
        from mxnet_tpu.ndarray.ndarray import NDArray

        opt = cfg["optimizer"]
        net = model.build(mx, cfg, weights, mx.tpu(0))
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
    if not args.skip_reference:
        train = {n: w for n, w in weights.items() if ref.trainable(n)}
        frozen = {n: w for n, w in weights.items() if n not in train}

        def ref_step(train, frozen, batch):
            def total(tr):
                return jnp.sum(ref.per_sample_loss(
                    cfg, {**tr, **frozen}, batch))
            return jax.value_and_grad(total)(train)

        t = time.perf_counter()
        compiled = jax.jit(ref_step).lower(
            *described((train, frozen, batch))).compile()
        _report("reference: float32 loss + gradients", compiled,
                time.perf_counter() - t)


if __name__ == "__main__":
    main()
