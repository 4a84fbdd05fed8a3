#!/usr/bin/env python
"""Sharded LLM serving (reference inference surface:
``src/c_api/c_predict_api.cc`` + ``benchmark_score.py`` [path cites —
unverified]; the TPU-era form is mesh-sharded prefill+decode).

Demonstrates the full serving recipe on a tensor-parallel mesh:
weights placed by the training rule table (a trained sharded state
serves without resharding), the KV cache materialized directly
sharded over the kv-head axis (`cache_specs`), chunked prefill with
``last_only`` (never pay for full-prompt logits), then a one-program
sampled decode loop — greedy, top-k, and nucleus.

Run: python example/inference/serve_llama.py    (8 virtual CPU devices)
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from dataclasses import replace
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh
    from mxtpu.parallel.sharding import shard_pytree

    n = len(jax.devices())
    if n < 2:
        print(f"needs >= 2 devices (have {n}); run with "
              "JAX_PLATFORMS=cpu "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return
    tp = 2  # tiny config has 2 kv heads; 1 per shard at tp=2
    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32, remat=False)
    mesh = pmesh.create_mesh(tp=tp,
                             devices=jax.devices()[:tp])
    params = shard_pytree(llama.init_params(cfg, jax.random.PRNGKey(0)),
                          mesh, llama.sharding_rules(cfg))

    batch, prompt_len, new_tokens = 4, 16, 24
    prompt = jax.device_put(
        jnp.asarray(np.random.default_rng(1).integers(
            0, cfg.vocab_size, (batch, prompt_len)), jnp.int32),
        NamedSharding(mesh, P()))

    # explicit prefill+decode (the server loop's shape): the cache is
    # born sharded — kv heads over tp — and donated between steps
    cache = llama.init_cache(cfg, batch, prompt_len + new_tokens,
                             mesh=mesh)
    print("cache k sharding:", cache["k"].sharding.spec)
    pf = jax.jit(lambda p, t, c: llama.prefill(
        cfg, p, t, c, mesh=mesh, last_only=True), donate_argnums=(2,))
    logits, cache = pf(params, prompt, cache)
    print(f"prefill: logits {logits.shape}, cache pos "
          f"{int(cache['pos'])}")
    step = jax.jit(lambda p, t, c: llama.decode_step(
        cfg, p, t, c, mesh=mesh), donate_argnums=(2,))
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    toks = [tok]
    for _ in range(4):                      # a few explicit steps...
        lg, cache = step(params, tok[:, None], cache)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        toks.append(tok)
    print("stepwise decode:", np.stack(
        [np.asarray(t) for t in toks], 1)[0])

    # ...and the one-program generate most callers want, with sampling
    t0 = time.perf_counter()
    gen = jax.jit(lambda p, t: llama.generate(
        cfg, p, t, new_tokens, mesh=mesh))
    out = gen(params, prompt)
    out.block_until_ready()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = gen(params, prompt)
    int(jax.device_get(out[0, -1]))         # honest fence
    dt = time.perf_counter() - t0
    print(f"greedy generate: {out.shape}, compile {compile_s:.1f}s, "
          f"steady {batch * new_tokens / dt:.0f} tok/s")

    sampled = jax.jit(lambda p, t: llama.generate(
        cfg, p, t, new_tokens, mesh=mesh, temperature=0.8, top_k=40,
        top_p=0.95, rng=jax.random.PRNGKey(7)))(params, prompt)
    same = float((np.asarray(sampled)[:, prompt_len:] ==
                  np.asarray(out)[:, prompt_len:]).mean())
    print(f"top-k/top-p sample vs greedy agreement: {same:.2f}")
    assert out.shape == (batch, prompt_len + new_tokens)

    # -- continuous batching (docs/serving.md): requests of MIXED
    # lengths join and leave the running batch at step boundaries —
    # the whole-batch generate above would drain to its stragglers
    from mxtpu.serve import Request, ServeEngine
    rng = np.random.default_rng(3)
    engine = ServeEngine(cfg, params, max_slots=4, max_len=48,
                         min_bucket=8, mesh=mesh)
    streamed = []
    rids = [engine.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, int(plen)),
        max_new_tokens=int(mnew), temperature=temp, seed=i,
        arrival_step=i,
        on_token=lambda rid, tok: streamed.append((rid, tok))))
        for i, (plen, mnew, temp) in enumerate(
            [(6, 8, 0.0), (14, 4, 0.8), (3, 12, 0.0), (9, 6, 0.9),
             (21, 3, 0.0), (5, 5, 0.7)])]
    results = engine.run()
    lat = engine.latency_stats()
    print(f"continuous batching: {len(rids)} mixed requests, "
          f"{engine.steps_run} steps, {engine.compile_count} compiles "
          f"(= {engine.n_buckets} prefill buckets + 1 decode), "
          f"p50 {lat['p50_token_ms']:.1f} ms/token")
    assert all(results[r].size > 0 for r in rids)
    assert len(streamed) == sum(results[r].size for r in rids)
    print("serving example OK")


if __name__ == "__main__":
    main()
