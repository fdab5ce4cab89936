"""HLO-level audit of the bench train step (VERDICT r4 task: perf audit
while the chip is unreachable).

Compiles the EXACT bench.py ResNet-50 train step on the CPU backend and
reports, from the optimized HLO:
  * every convolution: operand/result element types (bf16 on both sides
    = MXU-eligible), window/layout attributes;
  * dot ops and their dtypes;
  * convert (cast) population — stray f32 upcasts show up here;
  * donation: input-output aliasing actually established;
  * flop attribution: fwd vs fwd+bwd split via separate compiles.

Usage: python tools/hlo_audit.py [NHWC|NCHW] [batch]
Writes docs/perf_audit_r4_data.json and prints a summary.
"""
from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def audit(layout="NHWC", batch=256):
    import bench

    import jax
    import jax.numpy as jnp

    # a StableHLO audit is platform-neutral: it runs wherever this
    # process was started and names that platform in its report
    platform = jax.devices()[0].platform

    net, step, params, momenta, x, y = bench.build_resnet_train(
        layout, batch, donate=True)
    key = jax.random.PRNGKey(0)
    lowered = step.lower(params, momenta, x, y, key)
    # PLATFORM-NEUTRAL StableHLO: the optimized backend HLO on CPU
    # legalizes bf16 compute to f32 (CPU has no bf16 units), which says
    # nothing about the TPU compilation — audit what we HAND to XLA.
    shlo = lowered.as_text()
    compiled = lowered.compile()

    report = {"layout": layout, "batch": batch, "platform": platform}

    # stablehlo.convolution ... -> tensor<256x56x56x64xbf16>
    convs = re.findall(
        r"stablehlo\.convolution[^\n]*->\s*tensor<([\dx]+)x(\w+)>", shlo)
    report["n_convolutions"] = len(convs)
    report["conv_result_dtypes"] = sorted({t for _, t in convs})
    non_bf16 = [{"result_shape": s, "result_type": t}
                for s, t in convs if t != "bf16"]
    report["convs_not_bf16"] = non_bf16[:10]
    report["n_convs_not_bf16"] = len(non_bf16)

    dots = re.findall(
        r"stablehlo\.dot(?:_general)?[^\n]*->\s*tensor<[\dx]*x?(\w+)>",
        shlo)
    report["dot_result_dtypes"] = sorted(set(dots))

    # convert population by src->dst element count
    convert_pairs = {}
    for m in re.finditer(
            r"stablehlo\.convert[^\n]*:\s*\(tensor<([\dx]*?)x?(\w+)>\)"
            r"\s*->\s*tensor<[\dx]*?x?(\w+)>", shlo):
        dims, src, dst = m.groups()
        n_elem = 1
        for d in dims.split("x"):
            if d:
                n_elem *= int(d)
        k = f"{src}->{dst}"
        e = convert_pairs.setdefault(k, {"count": 0, "elements": 0})
        e["count"] += 1
        e["elements"] += n_elem
    report["converts_top"] = dict(sorted(
        convert_pairs.items(), key=lambda kv: -kv[1]["elements"])[:12])

    # elementwise dtype population in the program as written
    f32_ew = len(re.findall(
        r"stablehlo\.(add|multiply|subtract|divide|maximum|rsqrt|exp)"
        r"[^\n]*tensor<[\dx]*x?f32>", shlo))
    bf16_ew = len(re.findall(
        r"stablehlo\.(add|multiply|subtract|divide|maximum|rsqrt|exp)"
        r"[^\n]*tensor<[\dx]*x?bf16>", shlo))
    report["elementwise_f32_vs_bf16"] = {"f32": f32_ew, "bf16": bf16_ew}

    # donation: established aliasing is visible in compiled memory stats
    report["donation_note"] = "see memory.alias_bytes vs argument_bytes"
    try:
        mem = compiled.memory_analysis()
        report["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not expose it
        report["memory"] = str(e)

    d = compiled.cost_analysis()
    report["total_flops"] = float(d.get("flops", 0))

    # fwd-only flops for the fwd/bwd split
    fwd, p2 = net.as_pure_function(training=True)

    def fwd_loss(pd, key, x, y):
        out, _ = fwd(pd, key, x)
        logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    cf = jax.jit(fwd_loss).lower(params, key, x, y).compile()
    caf = cf.cost_analysis()
    df = caf[0] if isinstance(caf, list) else caf
    report["fwd_flops"] = float(df.get("flops", 0))
    report["bwd_over_fwd"] = round(
        (report["total_flops"] - report["fwd_flops"])
        / max(report["fwd_flops"], 1), 3)

    return report


def main():
    layout = sys.argv[1] if len(sys.argv) > 1 else "NHWC"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    rep = audit(layout, batch)
    suffix = "" if layout.upper() == "NHWC" else f"_{layout.lower()}"
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs",
        f"perf_audit_r4_data{suffix}.json")
    with open(out, "w") as f:
        json.dump(rep, f, indent=1)
    print(json.dumps(rep, indent=1)[:4000])


if __name__ == "__main__":
    main()
