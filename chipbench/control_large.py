#!/usr/bin/env python3
"""`control.py` for a cell of the ``train_step_large`` driver: the plain
reference in the next precision down against the float32 reference, both
through `reference/train_ref_large.py` (one after the other, each from
weights made anew), by the very numbers a run compares.

    python3 chipbench/control_large.py --workload <cell> --seeds 1,2,3
"""
import importlib

import control


def control_numbers(workload, cfg, seed, precision=None):
    """{check name: value} of the control against the reference, for one
    seed, at the cell's own size."""
    import weights as wmod
    from compare import train_numbers
    from reference import train_ref_large

    ref = importlib.import_module("reference." + cfg["builder"])
    precision = precision or cfg["control_precision"]
    specs = ref.param_specs(cfg)
    w_seed = wmod.weights_seed(cfg, seed)

    def make():
        return wmod.make_weights(specs, w_seed, cfg["dtype"])

    batches = importlib.import_module(
        "drivers." + workload["driver"]).reference_batches(
            cfg, workload["traffic_params"], seed, 3, ref)
    a = train_ref_large.train_steps(ref, cfg, make, batches, 3)
    b = train_ref_large.train_steps(ref, cfg, make, batches, 3, precision)
    weight_leaves = [n for n, shape, *_ in specs
                     if len(shape) >= 2 and ref.trainable(n)]
    out = {}
    for name, value, note in train_numbers(b, a, weight_leaves):
        out[name] = value
        if note and not name.startswith("loss"):
            out[name + ".leaf"] = note
    out["_leaves"] = {"losses": [b[0], a[0]], "grad_norm": [b[1], a[1]],
                      "dw_norm": [b[2], a[2]]}
    return out


if __name__ == "__main__":
    control.control_numbers = control_numbers   # control.main, these numbers
    control.main()
