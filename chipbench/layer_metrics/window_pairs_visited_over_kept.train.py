"""Kernels: query-key pairs in the sub-tiles the flash forward's span
schedule visits on a layer that slides a window, over the pairs the band
keeps — the program's gauge pair ``attention_pairs_visited{mask=window}``
/ ``attention_pairs_kept{mask=window}``, set on the host when the plan of
a signature is built, which is while the step is traced.  1 is a schedule
that spends nothing outside the band; at a window of two sub-tiles a
query tile visits three (one half-dead below the band, one whole, one
half-dead on the diagonal): 1.5.  None on a program without the gauges,
or one whose step planned no window."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    pair = [getattr(ti, "attention_pairs_" + name, None)
            for name in ("visited", "kept")]
    if None in pair:
        return None
    visited, kept = (dict(g.series()).get(("window",)) for g in pair)
    if visited is None or kept is None or not kept.value:
        return None
    return visited.value / kept.value
