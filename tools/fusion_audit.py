"""Fusion-level StableHLO audit of the bench train step (VERDICT r4 #1
fallback: the chip is unreachable, so quantify — from the program alone —
where the bytes go, and produce FALSIFIABLE predictions for each staged
A/B knob).

Method: parse the StableHLO `bench.py` hands to XLA into an SSA dataflow
graph, segment it into *predicted* TPU fusion regions (anchors =
convolution / dot_general / reduce-window ops, which XLA fuses
elementwise producers/consumers around; elementwise, convert, broadcast,
select, compare and friends merge into connected regions), then charge
each region its external bytes: inputs produced outside the region +
outputs consumed outside it. That is the HBM traffic IF XLA fuses the way
TPU normally does. The pessimistic column charges every op its full
operand+result bytes — the cost when fusion breaks.

Roofline uses the same v5e-class constants as BENCH_ESTIMATE.json
(197 TFLOP/s bf16, 819 GB/s HBM).

Usage: python tools/fusion_audit.py [NHWC|NCHW] [batch]
Writes docs/fusion_audit_r5_<layout>.json and prints the summary table.

`--report` switches to the PROMOTED byte model (the same
passes/memory.py estimator KernelPass and `MXTPU_KERNELS=auto` consult):
it captures a train-step jaxpr, ranks the predicted fusion regions by
external HBM bytes, annotates each with its bandwidth-kernel coverage —

  covered    a shipped Pallas kernel replaces this region family here
             (or already did: the region IS a pallas_call);
  fallback   a kernel targets the family but declines this site
             (shape/dtype outside the supported envelope);
  uncovered  no shipped kernel targets the region (MXU anchors, misc
             glue) — the candidate list for the next kernel;

and appends the analytic per-kernel predictions (XLA-path bytes vs
kernel floor, docs/kernels.md's decision table numbers).

    python tools/fusion_audit.py --report [--model mlp|resnet]
                                 [--json PATH]

`--model mlp` (default) is a Dense→BatchNorm→Dense step with a
multi-precision SGD ladder — every audited region family, small enough
to trace on CPU in seconds.
"""
from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.telemetry.instruments import device_peaks  # noqa: E402

# the chip this analytic model targets (the one peaks table of the repo)
_V5E = device_peaks("TPU v5 lite")
PEAK_FLOPS = _V5E["bf16_flops"]
HBM_BPS = _V5E["hbm_bytes_per_s"]

_ELEM_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "i64": 8,
               "i32": 4, "ui32": 4, "i8": 1, "ui8": 1, "i1": 0.125,
               "i16": 2, "ui16": 2, "f8E4M3FN": 1, "f8E5M2": 1}

# ops that root a fusion region on TPU (the MXU/reduce kernels)
_ANCHORS = ("convolution", "dot_general", "dot", "reduce_window",
            "select_and_scatter", "scatter", "gather", "sort",
            "dynamic_slice", "dynamic_update_slice", "iota", "rng",
            "fft", "custom_call")
# ops that fuse freely into neighbours
_FUSABLE = ("add", "multiply", "subtract", "divide", "maximum", "minimum",
            "rsqrt", "sqrt", "exponential", "exp", "log", "logistic",
            "tanh", "abs", "negate", "sign", "floor", "ceil", "convert",
            "broadcast_in_dim", "broadcast", "select", "compare", "and",
            "or", "not", "xor", "clamp", "reshape", "transpose", "slice",
            "concatenate", "pad", "reverse", "reduce", "power",
            "remainder", "is_finite", "round_nearest_even",
            "round_nearest_afz")


def _tensor_bytes(sig):
    """bytes of 'tensor<256x56x56x64xbf16>' (or '4x8xf32' inner)."""
    m = re.match(r"tensor<(.*)>", sig)
    inner = m.group(1) if m else sig
    parts = inner.split("x")
    dtype = parts[-1]
    n = 1
    for p in parts[:-1]:
        if p.isdigit():
            n *= int(p)
    return n * _ELEM_BYTES.get(dtype, 4), dtype


def parse_stablehlo(shlo):
    """Return list of ops: {id, name, operands[], out_bytes, out_dtype}.
    Only the main function's body is walked (sub-functions are inlined by
    the time jax lowers a jitted step; remaining funcs are tiny)."""
    ops = []
    for line in shlo.splitlines():
        line = line.strip()
        m = re.match(
            r"%(\S+?)\s*=\s*\"?stablehlo\.([\w.]+)\"?[^%]*(.*?)\s*:\s*"
            r"\(?(tensor<[^)]*?>)", line)
        if not m:
            continue
        rid, name, mid, first_sig = m.groups()
        operands = re.findall(r"%([\w#]+)", mid)
        # result signature: after '->' if present, else the single sig
        rm = re.search(r"->\s*(tensor<[^>]*>)", line)
        sig = rm.group(1) if rm else first_sig
        out_bytes, out_dtype = _tensor_bytes(sig)
        ops.append({"id": rid, "name": name, "operands": operands,
                    "bytes": out_bytes, "dtype": out_dtype})
    return ops


def fusion_regions(ops):
    """Union-find elementwise connected components; anchors isolate."""
    idx = {o["id"]: i for i, o in enumerate(ops)}
    parent = list(range(len(ops)))

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb

    def fusable(o):
        return any(o["name"].startswith(f) for f in _FUSABLE) \
            and not any(o["name"].startswith(a) for a in _ANCHORS)

    for i, o in enumerate(ops):
        if not fusable(o):
            continue
        for src in o["operands"]:
            j = idx.get(src)
            if j is not None and fusable(ops[j]):
                union(i, j)
    regions = {}
    for i, o in enumerate(ops):
        if fusable(o):
            regions.setdefault(find(i), []).append(i)
    return regions, idx


def audit(layout="NHWC", batch=256):
    import bench

    import jax

    # a StableHLO audit is platform-neutral: it runs wherever this
    # process was started and names that platform in its report
    platform = jax.devices()[0].platform

    net, step, params, momenta, x, y = bench.build_resnet_train(
        layout, batch, donate=True)
    key = jax.random.PRNGKey(0)
    lowered = step.lower(params, momenta, x, y, key)
    shlo = lowered.as_text()
    flops = float((lowered.compile().cost_analysis() or {}).get(
        "flops", 0)) if platform == "tpu" else None
    if flops is None:
        d = lowered.compile().cost_analysis()
        flops = float(d.get("flops", 0))

    ops = parse_stablehlo(shlo)
    regions, idx = fusion_regions(ops)
    consumers = {}
    for o in ops:
        for src in o["operands"]:
            consumers.setdefault(src, []).append(o["id"])

    region_rows = []
    fused_bytes = 0.0
    f32_elem_region_bytes = 0.0
    for rid, members in regions.items():
        mem_ids = {ops[i]["id"] for i in members}
        in_bytes = 0.0
        out_bytes = 0.0
        f32_share = 0
        for i in members:
            o = ops[i]
            if o["dtype"] == "f32":
                f32_share += 1
            for src in o["operands"]:
                j = idx.get(src)
                if j is None or ops[j]["id"] not in mem_ids:
                    in_bytes += ops[j]["bytes"] if j is not None else 0
            outside = [c for c in consumers.get(o["id"], [])
                       if c not in mem_ids]
            if outside or not consumers.get(o["id"]):
                out_bytes += o["bytes"]
        total = in_bytes + out_bytes
        fused_bytes += total
        if f32_share > len(members) // 2:
            f32_elem_region_bytes += total
        region_rows.append({"n_ops": len(members),
                            "hbm_bytes": total,
                            "mostly_f32": f32_share > len(members) // 2})

    anchor_bytes = 0.0
    n_anchors = 0
    for o in ops:
        if any(o["name"].startswith(a) for a in _ANCHORS):
            n_anchors += 1
            anchor_bytes += o["bytes"]
            for src in o["operands"]:
                j = idx.get(src)
                if j is not None:
                    anchor_bytes += ops[j]["bytes"]

    broken_bytes = sum(o["bytes"] for o in ops) + sum(
        ops[idx[s]]["bytes"] for o in ops for s in o["operands"]
        if s in idx)

    region_rows.sort(key=lambda r: -r["hbm_bytes"])
    report = {
        "layout": layout, "batch": batch, "platform": platform,
        "constants": {"peak_bf16_flops": PEAK_FLOPS,
                      "hbm_bytes_per_s": HBM_BPS},
        "n_ops_parsed": len(ops),
        "n_fusion_regions": len(regions),
        "n_anchor_kernels": n_anchors,
        "kernel_boundaries": len(regions) + n_anchors,
        "flops_per_step": flops,
        "t_flops_ms": flops / PEAK_FLOPS * 1e3,
        "fused_model": {
            "region_hbm_bytes": fused_bytes,
            "anchor_hbm_bytes": anchor_bytes,
            "total_hbm_bytes": fused_bytes + anchor_bytes,
            "t_hbm_ms": (fused_bytes + anchor_bytes) / HBM_BPS * 1e3,
        },
        "broken_model": {
            "total_hbm_bytes": broken_bytes,
            "t_hbm_ms": broken_bytes / HBM_BPS * 1e3,
        },
        "f32_elementwise_region_bytes": f32_elem_region_bytes,
        "f32_regions_t_hbm_ms": f32_elem_region_bytes / HBM_BPS * 1e3,
        "top_regions": region_rows[:15],
    }
    return report


# ---------------------------------------------------------------------------
# --report: the promoted byte model + kernel-coverage annotation
# ---------------------------------------------------------------------------


def _mlp_step(batch=256, features=512, hidden=512, nout=4):
    """A minimal train step exercising every audited region family: dot
    anchors, the BN-statistics fwd+bwd regions, and a multi-precision
    SGD ladder (bf16 params, f32 masters) through the production
    `Optimizer._fused_step_body` — so kernel sites dispatch exactly as
    they would in training.  Returns (step_fn, example_args)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import nn as mnn
    from mxnet_tpu.optimizer import SGD
    from mxnet_tpu.optimizer.optimizer import Optimizer

    w1 = jnp.zeros((features, hidden), jnp.bfloat16)
    w2 = jnp.zeros((hidden, max(nout, 8)), jnp.bfloat16)
    gamma = jnp.ones((hidden,), jnp.float32)
    beta = jnp.zeros((hidden,), jnp.float32)
    mm = jnp.zeros((hidden,), jnp.float32)
    mv = jnp.ones((hidden,), jnp.float32)
    masters = [w1.astype(jnp.float32), w2.astype(jnp.float32)]
    momenta = [jnp.zeros_like(m) for m in masters]
    x = jnp.zeros((batch, features), jnp.bfloat16)
    y = jnp.zeros((batch, w2.shape[1]), jnp.float32)
    hyper = {"momentum": 0.9, "rescale_grad": 1.0 / batch}

    def loss_fn(w1_, w2_, gamma_, beta_, x_, y_):
        h = x_ @ w1_
        o, _, _ = mnn.batch_norm(h, gamma_, beta_, mm, mv,
                                 training=True, axis=-1)
        p = jnp.maximum(o, 0) @ w2_
        d = p.astype(jnp.float32) - y_
        return jnp.mean(d * d)

    def step(w1_, w2_, gamma_, beta_, m1, m2, v1, v2, x_, y_):
        loss, gs = jax.value_and_grad(loss_fn, argnums=(0, 1, 2, 3))(
            w1_, w2_, gamma_, beta_, x_, y_)
        nws, nsts = Optimizer._fused_step_body(
            SGD, None, False, True,
            [w1_, w2_], [(m1, v1), (m2, v2)], [gs[0], gs[1]],
            [0.05, 0.05], [1e-4, 1e-4], [1, 1], None, hyper)
        ngb, _ = Optimizer._fused_step_body(
            SGD, None, False, False,
            [gamma_, beta_], [jnp.zeros_like(gamma_),
                              jnp.zeros_like(beta_)],
            [gs[2], gs[3]], [0.05, 0.05], [0.0, 0.0], [1, 1], None,
            hyper)
        return loss, nws, nsts, ngb

    args = (w1, w2, gamma, beta, masters[0], masters[1],
            momenta[0], momenta[1], x, y)
    return step, args


def _resnet_step(layout="NHWC", batch=256):
    import bench
    import jax

    net, step, params, momenta, x, y = bench.build_resnet_train(
        layout, batch, donate=False)
    key = jax.random.PRNGKey(0)
    return step, (params, momenta, x, y, key)


def _region_coverage(prims, bn_supported, opt_supported, anchor_prims):
    """Classify one predicted fusion region against the shipped kernels
    by primitive census — covered / fallback / uncovered."""
    names = set(prims)
    if "pallas_call" in names:
        return "covered"
    if names & anchor_prims:
        return "uncovered"
    if {"reduce_sum", "rsqrt"} & names:
        # a statistics region: the BN kernel family
        return "covered" if bn_supported else "fallback"
    if "convert_element_type" in names and names & {"mul", "add", "sub"}:
        # widening elementwise chain: the optimizer-ladder family
        return "covered" if opt_supported else "fallback"
    return "uncovered"


def report(model="mlp", json_path=None, batch=256):
    """The --report entry point; returns the report dict (also printed,
    optionally dumped to --json PATH)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import dispatch as kdispatch
    from mxnet_tpu.kernels import norm as knorm
    from mxnet_tpu.kernels import opt as kopt
    from mxnet_tpu.optimizer import SGD
    from mxnet_tpu.passes import memory as pmem

    if model == "mlp":
        step, args = _mlp_step(batch=batch)
        hidden = args[0].shape[1]
        h_sds = jax.ShapeDtypeStruct((batch, hidden), args[0].dtype)
        w_sds = jax.ShapeDtypeStruct(args[0].shape, args[0].dtype)
        m_sds = jax.ShapeDtypeStruct(args[0].shape, jnp.float32)
        bn_supported = knorm._supported(h_sds, h_sds.ndim - 1) is None
        opt_supported = kopt._supported(
            SGD, True, w_sds, (m_sds, m_sds), w_sds) is None
    else:
        step, args = _resnet_step(batch=batch)
        # per-site shapes vary across the net; annotate by family only
        bn_supported = opt_supported = True

    closed = jax.make_jaxpr(step)(*args)
    regions = pmem.estimate_region_bytes(closed)
    anchor_prims = set(pmem._ANCHOR_PRIMS)

    rows = []
    for r in regions:
        cov = _region_coverage(r["prims"], bn_supported, opt_supported,
                               anchor_prims)
        rows.append({
            "external_bytes": r["external_bytes"],
            "eqns": r["eqns"],
            "coverage": cov,
            "prims": dict(sorted(r["prims"].items(),
                                 key=lambda kv: -kv[1])[:6]),
        })
    # the estimator reports fusion REGIONS; anchors (MXU kernels, and —
    # once adopted — the Pallas kernels themselves) sit between regions.
    # List them too so kernel adoption is visible in the ranking.
    steps, token_bytes, _, _, _ = pmem._flatten_steps(closed)
    for prim, ins, outs in steps:
        if prim in anchor_prims:
            ext = (sum(token_bytes[t] for t in set(ins))
                   + sum(token_bytes[t] for t in set(outs)))
            rows.append({
                "external_bytes": ext,
                "eqns": 1,
                "coverage": "covered" if prim == "pallas_call"
                else "uncovered",
                "prims": {prim: 1},
            })
    rows.sort(key=lambda r: -r["external_bytes"])
    totals = {"covered": 0, "fallback": 0, "uncovered": 0}
    for rank, r in enumerate(rows, start=1):
        r["rank"] = rank
        totals[r["coverage"]] += r["external_bytes"]

    # analytic per-kernel predictions at this model's audited shapes
    # (the docs/kernels.md decision-table numbers, from recorded jaxprs)
    from mxnet_tpu.ops import nn as mnn

    def _bn_pred(shape, dtype):
        xs = jnp.zeros(shape, dtype)
        gs = jnp.zeros((shape[-1],), jnp.float32)
        cf = jax.make_jaxpr(
            lambda x, g, b, s: mnn._bn_train(x, g, b, s, 1e-5,
                                             len(shape) - 1))(xs, gs, gs, gs)

        def loss(x, g, b):
            o, m, v = mnn._bn_train(x, g, b, gs, 1e-5, len(shape) - 1)
            return (jnp.sum(o.astype(jnp.float32)) + jnp.sum(m)
                    + jnp.sum(v))

        cb = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(xs, gs, gs)
        xla = (sum(r["external_bytes"]
                   for r in pmem.estimate_region_bytes(cf))
               + sum(r["external_bytes"]
                     for r in pmem.estimate_region_bytes(cb)))
        _, floor = pmem.norm_region_bytes(shape, dtype, jnp.float32)
        return {"xla_bytes": int(xla), "kernel_bytes": int(floor),
                "predicted_reduction": round(1 - floor / xla, 4)}

    def _opt_pred(n, dtype, mp):
        from mxnet_tpu.optimizer.optimizer import Optimizer
        w = jnp.zeros((n,), dtype)
        mst = jnp.zeros((n,), jnp.float32)
        hyper = {"momentum": 0.9, "rescale_grad": 1.0}

        def one(w_, master, mom, g):
            st = (master, mom) if mp else mom
            return Optimizer._fused_param_step(
                SGD, 1.0, False, mp, w_, st, g, 0.01, 1e-4, 1, None,
                hyper)

        c = jax.make_jaxpr(one)(w, mst, mst, w)
        xla = sum(r["external_bytes"]
                  for r in pmem.estimate_region_bytes(c))
        _, floor = pmem.optimizer_region_bytes(n, dtype, 1, mp)
        return {"xla_bytes": int(xla), "kernel_bytes": int(floor),
                "predicted_reduction": round(1 - floor / xla, 4)
                if xla else 0.0}

    if model == "mlp":
        hidden = args[0].shape[1]
        kernels = {
            "bn_fwd_bwd": _bn_pred((batch, hidden), args[0].dtype),
            "optimizer_mp": _opt_pred(int(args[0].size),
                                      args[0].dtype, True),
            "optimizer_f32": _opt_pred(int(args[0].size),
                                       jnp.float32, False),
        }
    else:
        kernels = {
            "bn_fwd_bwd": _bn_pred((batch * 56 * 56, 256), jnp.bfloat16),
            "optimizer_mp": _opt_pred(1 << 20, jnp.bfloat16, True),
        }

    rep = {
        "model": model,
        "batch": batch,
        "mode": kdispatch.mode(),
        "platform": jax.devices()[0].platform,
        "n_regions": len(rows),
        "external_bytes_total": sum(r["external_bytes"] for r in rows),
        "coverage_bytes": totals,
        "kernels": kernels,
        "regions": rows[:20],
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rep, f, indent=1)
    return rep


def _print_report(rep):
    print(f"byte-model report: model={rep['model']} "
          f"mode={rep['mode']} platform={rep['platform']}")
    t = rep["coverage_bytes"]
    total = rep["external_bytes_total"] or 1
    print(f"  external bytes: {total / 1e6:.1f} MB  "
          f"(covered {t['covered'] / 1e6:.1f} / fallback "
          f"{t['fallback'] / 1e6:.1f} / uncovered "
          f"{t['uncovered'] / 1e6:.1f})")
    print("  kernels (predicted, XLA path vs kernel):")
    for name, k in rep["kernels"].items():
        print(f"    {name:14s} {k['xla_bytes'] / 1e6:8.1f} MB -> "
              f"{k['kernel_bytes'] / 1e6:8.1f} MB  "
              f"({k['predicted_reduction']:.0%} less)")
    print("  top regions:")
    for r in rep["regions"][:10]:
        prims = ",".join(list(r["prims"])[:4])
        print(f"    #{r['rank']:<3d} {r['external_bytes'] / 1e6:8.2f} MB "
              f"{r['coverage']:9s} {r['eqns']:3d} eqns  [{prims}]")


def main():
    if "--report" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--report"]

        def _opt(flag, default):
            if flag in argv:
                i = argv.index(flag)
                v = argv[i + 1]
                del argv[i:i + 2]
                return v
            return default

        model = _opt("--model", "mlp")
        json_path = _opt("--json", None)
        batch = int(_opt("--batch", "256"))
        rep = report(model=model, json_path=json_path, batch=batch)
        _print_report(rep)
        return
    layout = sys.argv[1] if len(sys.argv) > 1 else "NHWC"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    rep = audit(layout, batch)
    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", f"fusion_audit_r5_{layout.lower()}.json")
    with open(out, "w") as f:
        json.dump(rep, f, indent=1)
    slim = {k: v for k, v in rep.items() if k != "top_regions"}
    print(json.dumps(slim, indent=1))
    print("top regions by HBM bytes:")
    for r in rep["top_regions"][:8]:
        print(f"  {r['n_ops']:4d} ops  {r['hbm_bytes'] / 1e6:8.1f} MB  "
              f"{'f32' if r['mostly_f32'] else 'bf16'}")


if __name__ == "__main__":
    main()
