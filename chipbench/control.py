#!/usr/bin/env python3
"""The control of ``correct``: the plain reference, put in the program's
place and computed in the nearest precision BELOW the one the
configuration states (``control_precision``), compared with the float32
reference by the very numbers a run compares, each against the
configuration's own limit (`verdict`).  It has to come out as not
correct.  Not part of a benchmark run: it is read on the chip when a
limit is set (PERF.md gives the readings) and kept at a toy size in
tests/.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3
"""
import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


def control_numbers(workload, cfg, seed, precision=None):
    """{check name: value} of the control against the reference, for one
    seed, at the cell's own size."""
    import weights as wmod
    from compare import train_numbers
    from reference import train_ref

    ref = importlib.import_module("reference." + cfg["builder"])
    precision = precision or cfg["control_precision"]
    tp = workload["traffic_params"]
    weights = wmod.make_weights(ref.param_specs(cfg), seed, cfg["dtype"])
    # the batches the cell's first steps see, as its driver makes them
    batches = importlib.import_module(
        "drivers." + workload["driver"]).reference_batches(
            cfg, tp, seed, 3, ref)
    a = train_ref.train_steps(ref, cfg, weights, batches, 3)
    b = train_ref.train_steps(ref, cfg, weights, batches, 3, precision)
    weight_leaves = [n for n, w in weights.items()
                     if w.ndim >= 2 and ref.trainable(n)]
    out = {}
    for name, value, note in train_numbers(b, a, weight_leaves):
        out[name] = value
        if note and not name.startswith("loss"):
            out[name + ".leaf"] = note
    out["_leaves"] = {"losses": [b[0], a[0]], "grad_norm": [b[1], a[1]],
                      "dw_norm": [b[2], a[2]]}
    return out


def verdict(cfg, nums):
    """``correct`` as a run would read it: the control's numbers through
    `compare.Checks`, each beside the configuration's limit.  (correct,
    the names over their limits)."""
    from compare import Checks

    limits, checks = cfg["limits"]["train_step"], Checks()
    for name, value in nums.items():
        if name.startswith("_") or name.endswith(".leaf"):
            continue
        base, _, k = name.partition(".step")
        checks.add(name, value, limits[base][int(k) - 1] if k else limits[name])
    return checks.ok, [r["name"] for r in checks.rows if not r["ok"]]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default=None)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(os.path.dirname(HERE), ".jax_cache"))
    import mxnet_tpu  # noqa: F401  (the package's JAX defaults)

    with open(os.path.join(HERE, "workloads", args.workload + ".json")) as f:
        wl = json.load(f)
    with open(os.path.join(HERE, "configs", wl["config"] + ".json")) as f:
        cfg = json.load(f)
    for seed in [int(s) for s in args.seeds.split(",")]:
        nums = control_numbers(wl, cfg, seed, args.precision)
        leaves = nums.pop("_leaves", None)
        if args.dump and leaves:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(
                    args.dump, f"control.{args.workload}.{seed}.json"),
                    "w") as f:
                json.dump(leaves, f)
        correct, over = verdict(cfg, nums)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "precision": args.precision
                          or cfg["control_precision"], **nums,
                          "correct": correct, "over_limit": over}),
              flush=True)


if __name__ == "__main__":
    main()
