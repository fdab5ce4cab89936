"""Compile: the benchmark's spans around the first call of each warmed
shape, summed (trace + XLA compile or cache load + first run)."""


def read(trace, run):
    spans = [s["s"] for s in run["spans"] if s["compile"]]
    return sum(spans) if spans else None
