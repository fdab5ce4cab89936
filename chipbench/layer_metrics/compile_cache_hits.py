"""Compile: programs found in JAX's persistent cache during set-up
(jax.monitoring '/cache_hits' events)."""


def read(trace, run):
    ev = run.get("cache_events")
    return None if ev is None else ev["hits"]
