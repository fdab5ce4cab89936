"""The comparison that decides ``correct``.

Every number compared is printed beside its limit, in every run.  The
limits live in the configuration file, with the readings they were set
from in PERF.md.
"""
import json

import numpy as onp


class Checks:
    """Collects (name, value, limit); ``ok`` when every value is finite
    and at or under its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit, note=""):
        value = float(value)
        ok = bool(onp.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": ok, "note": note})
        print(json.dumps({"check": name, "value": value, "limit": limit,
                          "ok": ok, "note": note}), flush=True)
        return ok

    @property
    def ok(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def rel_gap(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def leaf_gaps(prog, ref, skip=()):
    """{leaf: |prog - ref| / max(ref, median ref)}: the gap between two
    NORMS of a leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    names = [n for n in sorted(ref) if n not in skip]
    r = onp.array([float(ref[n]) for n in names], onp.float64)
    p = onp.array([float(prog[n]) for n in names], onp.float64)
    gap = onp.abs(p - r) / onp.maximum(r, onp.median(r))
    gap = onp.where(onp.isfinite(gap), gap, onp.inf)
    return dict(zip(names, gap.tolist()))


def worst_leaf_gap(prog, ref, skip=()):
    """(largest gap over the leaves, name of that leaf)."""
    gaps = leaf_gaps(prog, ref, skip)
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def median_gap(prog, ref, leaves, skip=()):
    """Median of the gaps over ``leaves``.  Over the matmul and
    convolution weights this is the steady number that tells a lower
    precision apart: the worst leaf is always some normalisation scale or
    shift whose gradient is mostly rounding noise, in any precision."""
    gaps = leaf_gaps(prog, ref, skip)
    return float(onp.median([gaps[n] for n in leaves if n in gaps]))


def train_numbers(prog, ref, weight_leaves):
    """The numbers a training cell compares, from (losses, first-gradient
    norms, parameter-change norms) of the program and of the reference:
    [(name, value, note)].  A leaf whose reference gradient is nothing
    but rounding (under 1e-4 of the median leaf's: the loss does not
    depend on it) is left out of the parameter-change numbers, because an
    optimizer that normalises its step turns that rounding into a full
    step."""
    (pl, pg, pd), (rl, rg, rd) = prog, ref
    out = [(f"loss_rel.step{k + 1}", rel_gap(pl[k], rl[k]),
            f"program {pl[k]:.6g} reference {rl[k]:.6g}")
           for k in range(len(rl))]
    floor = 1e-4 * float(onp.median([float(v) for v in rg.values()]))
    free = tuple(n for n, v in rg.items() if float(v) <= floor)
    gap, leaf = worst_leaf_gap(pg, rg)
    out.append(("grad_norm_gap", gap, leaf))
    out.append(("grad_norm_gap.weights_median",
                median_gap(pg, rg, weight_leaves), ""))
    gap, leaf = worst_leaf_gap(pd, rd, free)
    out.append(("dw_norm_gap", gap, leaf))
    out.append(("dw_norm_gap.weights_median",
                median_gap(pd, rd, weight_leaves, free), ""))
    return out

