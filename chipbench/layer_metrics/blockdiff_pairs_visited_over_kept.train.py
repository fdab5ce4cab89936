"""Kernels: query-key pairs the flash forward computes on a layer under
the block-diffusion mask, over the pairs the mask keeps — the program's
gauge pair ``attention_pairs_visited{mask=block_diffusion}`` /
``attention_pairs_kept{mask=block_diffusion}``, set on the host when the
plan of a signature is built, which is while the step is traced.  1 is a
walk that spends nothing on dead pairs.  At (4, 4096) over 8,192
positions in sub-tiles of 1,024 the schedule visits 24 sub-tiles a head
for the 16.016 the mask keeps, 1.4985, where a masked sub-tile is
computed whole; a program that skips what is wholly dead inside one
reads less.  None on a program without the gauges, or one whose step
planned no such mask."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    pair = [getattr(ti, "attention_pairs_" + name, None)
            for name in ("visited", "kept")]
    if None in pair:
        return None
    visited, kept = (dict(g.series()).get(("block_diffusion",))
                     for g in pair)
    if visited is None or kept is None or not kept.value:
        return None
    return visited.value / kept.value
