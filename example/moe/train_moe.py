#!/usr/bin/env python
"""Mixture-of-Experts training with expert parallelism (round-4 NEW
capability; no reference counterpart — SURVEY §2.4 listed expert
parallelism as the strategy the reference era lacked).

A tiny MoE llama (4 SwiGLU experts per layer, top-2 routing) trains on
a dp×ep×tp mesh: expert banks sharded over ``ep``, the load-balancing
aux loss keeping routing spread, and the SAME weights then serve
through the dropless decode path.

Run: python example/moe/train_moe.py        (8 virtual CPU devices)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    import optax
    from dataclasses import replace
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep

    if len(jax.devices()) < 8:
        print(f"needs 8 devices (have {len(jax.devices())}); run with "
              "JAX_PLATFORMS=cpu "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return

    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                  attn_impl="dense", remat=False,
                  moe_experts=4, moe_top_k=2, moe_capacity=2.0)
    mesh = pmesh.create_mesh(dp=2, ep=2, tp=2)
    rules = llama.sharding_rules(cfg)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adam(5e-3)
    state = pstep.init_state(params, tx, mesh, rules)
    step = pstep.make_train_step(llama.loss_fn(cfg, mesh), tx, mesh,
                                 rules)

    # a memorizable corpus: fixed token sequences
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 48)),
                         jnp.int32)
    losses = []
    for i in range(30):
        state, loss = step(state, {"tokens": tokens})
        losses.append(float(jax.device_get(loss)))
        if i % 10 == 0:
            print(f"step {i}: loss {losses[-1]:.4f}", flush=True)
    print(f"final loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0] * 0.5, "MoE failed to train"

    # the expert banks really live ep-sharded
    wg = state.params["layers"]["w_gate"]
    shard_E = wg.sharding.shard_shape(wg.shape)[1]
    print(f"expert bank {wg.shape[1]} experts, {shard_E} per ep shard")
    assert shard_E == cfg.moe_experts // 2

    # serve the trained weights: sharded dropless decode on the mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    prompt = jax.device_put(tokens[:4, :8],
                            NamedSharding(mesh, P(("dp", "fsdp"))))
    out = jax.jit(lambda p, t: llama.generate(
        cfg, p, t, 8, mesh=mesh))(state.params, prompt)
    # after memorizing the corpus, greedy continuation reproduces it
    got = np.asarray(out)[:, 8:16]
    want = np.asarray(tokens[:4, 8:16])
    acc = float((got == want).mean())
    print(f"greedy continuation accuracy vs memorized corpus: {acc:.2f}")
    assert acc > 0.8, acc
    print("moe example OK")


if __name__ == "__main__":
    main()
