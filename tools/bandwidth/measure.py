#!/usr/bin/env python
"""Allreduce bandwidth probe (reference ``tools/bandwidth/measure.py``
[path cite — unverified], a BASELINE.json metric).

Times psum over the local device mesh for a range of sizes and reports
algorithmic bandwidth (2(n-1)/n * bytes / time for a ring). On one chip
the collective is the identity; the probe then reports device memory
bandwidth of the copy, still useful as a smoke number.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def measure(sizes_mb, iters=10):
    devs = jax.devices()
    n = len(devs)
    mesh = jax.sharding.Mesh(np.array(devs), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    inv_n = 1.0 / n

    def many_psum(x):
        # iters collectives INSIDE one program: per-dispatch latency
        # would otherwise swamp the small sizes. pmean keeps magnitude
        # stable so the chain can't be folded away.
        def body(_, c):
            red = jax.lax.psum(c, "x") * jnp.float32(inv_n)
            # psum output is replicated over x; mark it varying again so
            # the loop carry type stays stable
            return jax.lax.pcast(red, ("x",), to="varying")
        return jax.lax.fori_loop(0, iters, body, x)

    shard = jax.shard_map(many_psum, mesh=mesh, in_specs=P("x"),
                          out_specs=P("x"))
    jshard = jax.jit(shard)
    # the fence: host read-back of a scalar of the result
    reduce1 = jax.jit(lambda y: y[0])

    def fence(y):
        return float(jax.device_get(reduce1(y)))

    rows = []
    for mb in sizes_mb:
        # mb is what EACH device contributes to the psum
        elems = max(int(mb * 1024 * 1024 / 4), 1)
        x = jax.device_put(
            jnp.ones((n * elems,), jnp.float32),
            NamedSharding(mesh, P("x")))
        fence(jshard(x))                       # compile
        t0 = time.perf_counter()
        fence(jshard(x))
        dt = (time.perf_counter() - t0) / iters
        nbytes = elems * 4
        algo_bw = (2 * (n - 1) / max(n, 1)) * nbytes / dt / 1e9 \
            if n > 1 else nbytes / dt / 1e9
        rows.append((mb, dt * 1e3, algo_bw))
        print(f"size {mb:8.2f} MB  time {dt*1e3:8.3f} ms  "
              f"busbw {algo_bw:8.2f} GB/s")
    return rows


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--sizes", default="1,4,16,64,256")
    p.add_argument("--iters", type=int, default=10)
    a = p.parse_args()
    print(f"devices: {jax.devices()}")
    measure([float(s) for s in a.sizes.split(",")], a.iters)
