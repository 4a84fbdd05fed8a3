"""Pipeline parallelism over the ``pp`` mesh axis — NEW capability, no
reference counterpart (SURVEY.md §2.4: "Pipeline parallelism (PP): NO
— NEW in rebuild: stage via shard_map + collective_permute
microbatching").

GPipe-style schedule: the layer stack (stacked params, leading layer
dim) is split into S contiguous stages, one per ``pp``-axis device.
Microbatches enter stage 0 one per tick; each tick every stage applies
its layers to the microbatch it holds, then the activations rotate one
stage forward via ``lax.ppermute``. After M + S - 1 ticks every
microbatch has crossed every stage. The whole schedule is ONE jitted
program — XLA overlaps each tick's compute with the permute's ICI
transfer, and the backward pass is the exact transpose schedule
(ppermute's transpose is the reverse rotation), so ``jax.grad``
through the pipeline just works.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe"]


def gpipe(layer_fn: Callable[[Any, Any], Any], stacked_params: Any, x,
          *, mesh: Mesh, n_microbatches: int, axis: str = "pp"):
    """Run ``x`` through a stack of layers pipelined over ``axis``.

    ``layer_fn(layer_params, x) -> x`` applies ONE layer.
    ``stacked_params``: pytree whose leaves have a leading layer dim L
    (the scan-over-layers layout llama/bert already use); L must
    divide by the stage count. ``x``: (B, ...) with B divisible by
    ``n_microbatches``. Returns (B, ...), replicated.
    """
    S = mesh.shape[axis]
    if S == 1:
        def apply_all(xx):
            def body(c, lp):
                return layer_fn(lp, c), None
            return lax.scan(body, xx, stacked_params)[0]
        return apply_all(x)

    M = n_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if L % S:
        raise ValueError(
            f"layer count {L} not divisible by {S} pipeline stages")
    mb = x.reshape((M, B // M) + x.shape[1:])

    param_specs = jax.tree.map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), stacked_params)

    def pp_fn(local_params, mb_all):
        # local_params: this stage's (L/S, ...) slice; mb_all: all
        # microbatches (replicated — only stage 0 reads them)
        stage = lax.axis_index(axis)
        zero_mb = jnp.zeros_like(mb_all[0])

        def apply_stage(xx):
            def body(c, lp):
                return layer_fn(lp, c), None
            return lax.scan(body, xx, local_params)[0]

        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outbuf = carry
            # stage 0 ingests microbatch t (zeros after the last one)
            inp = lax.cond(t < M, lambda: mb_all[jnp.minimum(t, M - 1)],
                           lambda: zero_mb)
            xx = jnp.where(stage == 0, inp, state)
            yy = apply_stage(xx)
            # the LAST stage finishes microbatch t-(S-1) at tick t
            done_idx = t - (S - 1)
            write = (stage == S - 1) & (done_idx >= 0)
            outbuf = lax.cond(
                write,
                lambda ob: ob.at[jnp.maximum(done_idx, 0)].set(yy),
                lambda ob: ob, outbuf)
            state = lax.ppermute(yy, axis, perm)
            return (state, outbuf), None

        outbuf0 = jnp.zeros((M,) + zero_mb.shape, zero_mb.dtype)
        (_, outbuf), _ = lax.scan(
            tick, (zero_mb, outbuf0), jnp.arange(M + S - 1))
        # outbuf is populated only on the last stage: one psum
        # assembles it everywhere (all other stages contribute zeros)
        outbuf = jnp.where(stage == S - 1, outbuf, 0)
        return lax.psum(outbuf, axis)

    out = jax.shard_map(pp_fn, mesh=mesh,
                        in_specs=(param_specs, P()), out_specs=P(),
                        check_vma=False)(stacked_params, mb)
    return out.reshape((B,) + x.shape[1:])
