#!/usr/bin/env python
"""Collective-bandwidth measurement (reference: tools/bandwidth/measure.py,
which timed kvstore push/pull per batch).

Times a jitted psum allreduce over every local device for a sweep of tensor
sizes and reports algorithmic bandwidth (2*(n-1)/n * bytes / time — the
ring-allreduce model the scaling book uses for ICI). On the CPU test mesh
this validates the harness; on a pod slice it measures real ICI.

  python tools/bandwidth.py [--sizes-mb 1 4 16 64] [--iters 10]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def measure(sizes_mb, iters=10, warmup=2):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Pspec


    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs).reshape(n), ("dp",))
    results = []
    for mb in sizes_mb:
        elems = int(mb * (1 << 20) / 4)
        x = jnp.ones((n, elems), jnp.float32)
        sharded = jax.device_put(
            x, NamedSharding(mesh, Pspec("dp", None)))

        @jax.jit
        def allreduce(v):
            return jax.shard_map(
                lambda s: jax.lax.psum(s, "dp"), mesh=mesh,
                in_specs=Pspec("dp", None), out_specs=Pspec(None, None),
            )(v)

        allreduce(sharded).block_until_ready()
        for _ in range(warmup):
            allreduce(sharded).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            allreduce(sharded).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        nbytes = elems * 4
        algo_bw = 2 * (n - 1) / n * nbytes / dt / 1e9
        results.append({"size_mb": mb, "n_devices": n,
                        "time_ms": dt * 1e3, "algo_bw_gbps": algo_bw})
        print(json.dumps(results[-1]))
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes-mb", type=float, nargs="+",
                   default=[1, 4, 16, 64])
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args(argv)
    measure(args.sizes_mb, args.iters)


if __name__ == "__main__":
    main()
