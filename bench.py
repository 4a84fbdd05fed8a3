"""Headline benchmarks: ResNet-50 img/s + BERT-base samples/s + Llama
tok/s, all on the full jitted train step with donated buffers. Every
timed region ends in a host read-back of a value the last step
produced, which cannot return before the device has finished.

Covers all three BASELINE.md headline configs (2: ResNet-50, 3:
BERT-base pretrain, 5: Llama causal-LM). The reference's equivalents
are ``example/image-classification/benchmark_score.py`` and the
``docs/faq/perf.md`` training tables [path cites — unverified].

Prints ONE JSON line. The headline metric stays ResNet-50 img/s/chip
(vs the recalled 1×V100 fp32 ~360 img/s, BASELINE.md); BERT and Llama
ride in "extra" with their own vs_baseline:
- bert: vs per-A100-chip ~250 samples/s (8×A100 "within 10%" north
  star ⇒ ~2000 total / 8).
- llama: no reference counterpart exists (SURVEY §2.4), so
  vs_baseline is null; the honest utilization number is the separate
  "mfu" field (vs the bf16 peak the perfscope table holds for this
  process's device_kind).
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

BASELINE_RESNET_IMG_S = 360.0   # reference 1×V100 fp32 (BASELINE.md)
BASELINE_BERT_SAMPLES_S = 250.0  # per-A100 share of the 8×A100 target


def _peak_flops():
    """bf16 peak of the devices this process measures on — the train
    benches mesh over all of them (``dp=-1``) — from the one peaks
    table, by ``device_kind``. A kind the table lacks raises."""
    from mxtpu.telemetry import perfscope
    return (perfscope.spec_for(jax.devices()[0].device_kind).peak_flops
            * jax.device_count())


def run_metadata():
    """Self-describing run context stamped into every emitted record
    (ISSUE 5 satellite): a BENCH_*.json entry must answer what jax,
    what silicon, how many devices, and whether the measured program
    recompiled mid-run — without cross-referencing the driver logs."""
    from mxtpu import telemetry
    from mxtpu.telemetry import perfscope
    dev = jax.devices()[0]
    reg = telemetry.registry()
    recompiles = sum(
        child.value
        for fam in reg.families() if fam.name == "recompile_total"
        for child in fam.children.values())
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "n_devices": jax.device_count(),
        "mesh_shape": {"dp": jax.device_count()},   # the headline
        # benches' default mesh; multi-axis configs also carry their
        # own "mesh" field in-record
        "telemetry_enabled": telemetry.enabled(),
        "telemetry": {
            "compile_total": int(reg.value("jax_compile_total")),
            "recompile_total": int(recompiles),
        },
        # per-program cost-model snapshot (ISSUE 13): every watched or
        # AOT-profiled program this process compiled, from the SAME
        # perfscope catalog the live gauges read
        "programs": {
            name: {"flops": c.flops, "bytes_accessed": c.bytes_accessed,
                   "peak_hbm_bytes": c.peak_hbm_bytes,
                   "roofline": c.klass}
            for name, c in sorted(perfscope.catalog().items())
        },
    }


def _time_steps(step_fn, state, batch, warmup=3, steps=20):
    for _ in range(warmup):
        state, loss = step_fn(state, batch)
    float(jax.device_get(loss))          # drain the queue
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = step_fn(state, batch)
    float(jax.device_get(loss))          # the fence
    return (time.perf_counter() - t0) / steps


def bench_resnet(batch=256, steps=30, stem=None):
    """ResNet-50 train step. ``stem`` defaults to the TPU-aware choice
    (s2d on accelerator backends, std on CPU; MXTPU_RESNET_STEM
    overrides — docs/env_var.md). Both stems are the SAME model (exact
    kernel rewrite, see mxtpu/models/resnet.py), so img/s are directly
    comparable and MFU uses the same useful-FLOP numerator (the s2d
    kernel's structurally-zero taps are not useful work)."""
    from mxtpu.models import resnet
    from mxtpu.parallel import mesh as pmesh, step as pstep
    from mxtpu.parallel.sharding import ShardingRules, P

    stem = stem or resnet.default_stem()
    cfg = resnet.CONFIGS["resnet50_s2d" if stem == "s2d" else "resnet50"]
    mesh = pmesh.create_mesh(dp=-1)
    rules = ShardingRules([(r".*", P())])
    params = resnet.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.sgd(0.1, momentum=0.9)
    state = pstep.init_state(params, tx, mesh, rules,
                             model_state=resnet.init_state(cfg))
    train_step = pstep.make_train_step(
        resnet.loss_fn(cfg), tx, mesh, rules, has_state=True)

    rng = np.random.default_rng(0)
    data = {"image": jnp.asarray(
                rng.standard_normal((batch, 224, 224, 3), np.float32),
                jnp.bfloat16),
            "label": jnp.asarray(rng.integers(0, cfg.num_classes, batch),
                                 jnp.int32)}
    dt = _time_steps(train_step, state, data, steps=steps)
    img_s = batch / dt
    # 23.9 GFLOP per image for a full train step: 3× the forward's
    # 7.96 GFLOP/img per XLA cost_analysis (2-FLOPs-per-MAC units,
    # consistent with the peaks table — the folklore "4.1 GFLOPs"
    # figure counts MACs)
    from mxtpu.telemetry import perfscope
    mfu = perfscope.mfu(batch * 23.9e9, dt, peak_flops=_peak_flops())
    return img_s, mfu, stem


def _dense_param_count(params, exclude_keys):
    """Parameter count for MFU math, excluding embedding tables
    (lookups are gathers, ~0 matmul FLOPs)."""
    total = excl = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        n = leaf.size
        total += n
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if any(e in name for e in exclude_keys):
            excl += n
    return total, total - excl


def bench_bert(batch=128, seq=128, n_mlm=20, steps=20):
    from mxtpu.models import bert
    from mxtpu.parallel import mesh as pmesh, step as pstep

    cfg = bert.CONFIGS["bert_base"]
    mesh = pmesh.create_mesh(dp=-1)
    rules = bert.sharding_rules(cfg)
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(1e-4)
    state = pstep.init_state(params, tx, mesh, rules)
    train_step = pstep.make_train_step(bert.loss_fn(cfg), tx, mesh, rules)

    rng = np.random.default_rng(0)
    batch_d = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                           (batch, seq)), jnp.int32),
        "mask": jnp.ones((batch, seq), jnp.float32),
        "mlm_positions": jnp.asarray(
            np.sort(rng.integers(0, seq, (batch, n_mlm))), jnp.int32),
        "mlm_labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (batch, n_mlm)), jnp.int32),
        "mlm_weights": jnp.ones((batch, n_mlm), jnp.float32),
        "nsp_labels": jnp.zeros((batch,), jnp.int32),
    }
    dt = _time_steps(train_step, state, batch_d, steps=steps)
    samples_s = batch / dt
    # MFU counts only dense-matmul work: encoder weights at all seq
    # positions, the tied vocab decode at the n_mlm positions only,
    # and 12·L·d·s² for attention; embedding gathers are ~0 FLOPs
    _, n_dense = _dense_param_count(
        params, ("tok_emb", "pos_emb", "type_emb"))
    flops_per_step = (6 * n_dense * batch * seq +
                      6 * cfg.dim * cfg.vocab_size * batch * n_mlm +
                      12 * cfg.n_layers * cfg.dim * seq * batch * seq)
    from mxtpu.telemetry import perfscope
    mfu = perfscope.mfu(flops_per_step, dt, peak_flops=_peak_flops())
    return samples_s, mfu


def bench_llama(batch=4, seq=2048, steps=15, cfg=None):
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep

    # ~500M-param config sized for one v5e chip's HBM (the 8B headline
    # config is a multi-chip job; MFU transfers). dim 2048 keeps every
    # weight-matmul output dim ≥ 2048 — this chip's matmul throughput
    # scales with the minor output dim (docs/perf.md N-sweep), so wider-
    # shallower beats deeper-narrower at equal params. dots_no_batch
    # remat saves weight-matmul outputs instead of recomputing the
    # whole layer (~3% step win measured).
    cfg = cfg or llama.LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=8, n_heads=16,
        n_kv_heads=8, hidden_dim=5632, max_seq_len=seq,
        attn_impl="flash", remat=True, remat_policy="dots_no_batch")
    mesh = pmesh.create_mesh(dp=-1)
    rules = llama.sharding_rules(cfg)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(3e-4)
    state = pstep.init_state(params, tx, mesh, rules)
    train_step = pstep.make_train_step(
        llama.loss_fn(cfg, mesh=mesh), tx, mesh, rules)

    rng = np.random.default_rng(0)
    batch_d = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)}
    dt = _time_steps(train_step, state, batch_d, warmup=2, steps=steps)
    tokens_s = batch * seq / dt
    # 6·N_dense per token (tok_embed gather excluded; lm_head is a real
    # matmul and stays) + causal attention ≈ 6·L·d·s per token
    n_params, n_dense = _dense_param_count(params, ("tok_embed",))
    flops_per_token = 6 * n_dense + 6 * cfg.n_layers * cfg.dim * seq
    from mxtpu.telemetry import perfscope
    mfu = perfscope.mfu(batch * seq * flops_per_token, dt,
                        peak_flops=_peak_flops())
    return tokens_s, mfu, n_params


def bench_llama_decode(batch=32, prompt=128, new_tokens=256, reps=3,
                       int8=False):
    """Autoregressive decode tok/s with the KV cache (VERDICT r2 #4):
    one jitted generate program (prefill + lax.scan of decode steps).
    ``int8=True`` serves weight-only int8 (quantize_params_int8,
    in-program dequant) — measured +14% over bf16-stored weights even
    at this 509M scale (r5; the r4 'shape-bound, buys nothing'
    verdict belonged to the older dequant formulation)."""
    from mxtpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=8, n_heads=16,
        n_kv_heads=8, hidden_dim=5632, max_seq_len=prompt + new_tokens,
        remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if int8:
        params = llama.quantize_params_int8(cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt),
                              0, cfg.vocab_size)
    gen = jax.jit(lambda p, t: llama.generate(cfg, p, t, new_tokens))
    out = gen(params, toks)
    int(jax.device_get(out[0, -1]))          # compile + drain
    t0 = time.perf_counter()
    for _ in range(reps):
        out = gen(params, toks)
    int(jax.device_get(out[0, -1]))          # the fence
    dt = (time.perf_counter() - t0) / reps
    return batch * new_tokens / dt


class _KVSampler:
    """Background poll of ``engine.kv_cache_stats()`` over a timed
    region: occupancy/active/pages peak while slots are LIVE, but the
    bench can only read stats after ``run()`` drains — by which point
    everything is free again. ~5 ms cadence; stats are host
    arithmetic under the engine lock, so sampling never syncs the
    device."""

    def __init__(self, engine):
        self._engine = engine
        self._stop = threading.Event()
        self.peak_active = 0
        self.peak_occupancy = 0.0
        self.peak_pages_used = 0
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.wait(0.005):
            kv = self._engine.kv_cache_stats()
            self.peak_active = max(self.peak_active, kv["active"])
            self.peak_occupancy = max(self.peak_occupancy,
                                      kv["occupancy"])
            self.peak_pages_used = max(self.peak_pages_used,
                                       kv["pages_used"])

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(2.0)


def bench_llama_serve(n_requests=48, max_slots=16, max_len=768,
                      mean_interarrival_steps=4.0, seed=0, int8=False,
                      cfg=None, page_size=None, n_pages=None,
                      shared_prefix=0):
    """Continuous-batching serving throughput + per-token latency
    (ISSUE 4 tentpole): the same ~500M decode config served through
    ``mxtpu.serve.ServeEngine`` under a SEEDED Poisson arrival stream
    of mixed prompt/output lengths — the regime where whole-batch
    ``generate`` drains to its stragglers and the slot engine keeps
    the decode program at full batch. Reports tok/s over generated
    tokens plus p50/p99 per-token latency (inter-token gaps), the KV
    occupancy the stream actually reached and the page pool's and
    prefix cache's stats. ``shared_prefix=N`` prepends one fixed
    N-token system prompt to every request — the prefix-sharing
    workload (hits > 0 once the first admission registers it)."""
    from mxtpu.models import llama
    from mxtpu.serve import Request, ServeEngine

    cfg = cfg or llama.LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=8, n_heads=16,
        n_kv_heads=8, hidden_dim=5632, max_seq_len=max_len,
        remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if int8:
        params = llama.quantize_params_int8(cfg, params)
    rng = np.random.default_rng(seed)
    engine = ServeEngine(cfg, params, max_slots=max_slots,
                         max_len=max_len,
                         min_bucket=max(4, max_len // 12),
                         page_size=page_size, n_pages=n_pages)
    prefix = (rng.integers(0, cfg.vocab_size, shared_prefix)
              if shared_prefix else None)

    def prompt_of(plen):
        tail = rng.integers(0, cfg.vocab_size, plen)
        return (np.concatenate([prefix, tail]) if prefix is not None
                else tail)

    # warmup: compile every prefill bucket the stream will use plus
    # the decode program BEFORE the timed region (the other benches'
    # 'compile + drain' discipline) — otherwise tok/s and the p99
    # inter-token gap are dominated by compile stalls
    for j, plen in enumerate([max_len // 12, max_len // 6,
                              max_len // 3, max_len // 2]):
        engine.submit(Request(
            prompt=prompt_of(plen), max_new_tokens=2, seed=j))
    engine.run()
    engine.reset_stats()
    arrival = 0.0
    total_new = 0
    for _ in range(n_requests):
        # mixed lengths scaled off max_len (768 default: prompts
        # 64-384, outputs 8-256); prompt + output always fits
        plen = int(rng.choice([max_len // 12, max_len // 6,
                               max_len // 3, max_len // 2]))
        mnew = int(rng.integers(
            8, (max_len - shared_prefix) // 3 + 1))
        total_new += mnew
        engine.submit(Request(
            prompt=prompt_of(plen), max_new_tokens=mnew,
            arrival_step=int(arrival)))
        arrival += rng.exponential(mean_interarrival_steps)
    t0 = time.perf_counter()
    with _KVSampler(engine) as sampler:
        engine.run()
    dt = time.perf_counter() - t0
    lat = engine.latency_stats()
    kv = engine.kv_cache_stats()
    hits, misses = kv["prefix_hits"], kv["prefix_misses"]
    rec = {"metric": "llama_500m_serve_tokens_per_s"
                     + ("_int8" if int8 else "")
                     + ("_shared_prefix" if shared_prefix else ""),
           "value": round(total_new / dt, 1), "unit": "tok/s",
           "p50_token_ms": round(lat["p50_token_ms"], 2),
           "p99_token_ms": round(lat["p99_token_ms"], 2),
           "n_requests": n_requests, "max_slots": max_slots,
           "steps": engine.steps_run,
           "compiles": engine.compile_count,
           "buckets": engine.n_buckets,
           "kv_occupancy_ratio": round(sampler.peak_occupancy, 4),
           "peak_active_slots": sampler.peak_active,
           "total_s": round(dt, 1), "vs_baseline": None,
           "pages_total": kv["pages_total"],
           "peak_pages_used": sampler.peak_pages_used,
           "pages_shared": kv["pages_shared"],
           "cow_forks": kv["cow_forks"],
           "prefix_hits": hits,
           "prefix_hit_rate": round(
               hits / (hits + misses), 4) if hits + misses else 0.0}
    return rec


def bench_spec(speculate_k=4, mnew=200, n_requests=6, max_slots=2):
    """Speculative decoding A/B (ISSUE 19 tentpole): the SAME paged
    engine config run twice — ``speculate_k=K`` against ``k=0`` — over
    a decode-predictable greedy workload (prompts whose continuations
    go periodic within a few tokens, the repetitive-output regime
    n-gram drafting exists for). Reports accepted tokens per slot-step,
    tok/s, and inter-token p50/p99 from the engine's own latency
    histogram, and gates the tentpole contract: the spec streams are
    BIT-IDENTICAL to the k=0 baseline, the accepted-token rate clears
    2 tok/step, and wall-clock tok/s strictly beats the baseline."""
    from dataclasses import replace as _replace
    from mxtpu.models import llama
    from mxtpu.serve import Request, ServeEngine

    cfg = _replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                   remat=False, attn_impl="dense", max_seq_len=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    # both prompts hit a short-period greedy plateau within ~10 tokens
    # (found by scanning tiny-model continuations) — the drafter's
    # periodic n-gram extension then proposes the full budget
    prompts = [[140, 141, 140], [175, 243, 166]]

    def one_mode(k):
        engine = ServeEngine(cfg, params, max_len=256, min_bucket=8,
                             max_slots=max_slots, page_size=16,
                             speculate_k=k)
        streams: dict = {}

        def cb(i):
            def on_token(rid, tok):
                streams.setdefault(i, []).append(int(tok))
            return on_token

        # warmup: prefill bucket + decode + (k>0) the verify program,
        # long enough to reach the plateau so drafting actually fires
        engine.submit(Request(prompt=np.asarray(prompts[0], np.int32),
                              max_new_tokens=16))
        engine.run()
        engine.reset_stats()
        total = 0
        for i in range(n_requests):
            engine.submit(Request(
                prompt=np.asarray(prompts[i % len(prompts)], np.int32),
                max_new_tokens=mnew, on_token=cb(i)))
            total += mnew
        t0 = time.perf_counter()
        engine.run()
        dt = time.perf_counter() - t0
        lat = engine.latency_stats()
        kv = engine.kv_cache_stats()
        return streams, {
            "toks_per_s": round(total / dt, 1),
            "accepted_tok_per_step": round(
                total / max(1, engine.steps_run) / max_slots, 2),
            "steps": engine.steps_run,
            "p50_token_ms": round(lat["p50_token_ms"], 3),
            "p99_token_ms": round(lat["p99_token_ms"], 3),
            "accept_rate": round(kv.get("spec_accept_rate", 0.0), 3),
            "compile_count": engine.compile_count}

    base_streams, base = one_mode(0)
    spec_streams, spec = one_mode(speculate_k)
    assert spec_streams == base_streams, \
        "speculative streams diverged from the k=0 baseline"
    assert spec["accepted_tok_per_step"] > 2.0, spec
    assert spec["toks_per_s"] > base["toks_per_s"], (base, spec)
    return {"metric": "llama_tiny_spec_decode_tokens_per_s",
            "value": spec["toks_per_s"], "unit": "tok/s",
            "speculate_k": speculate_k, "n_requests": n_requests,
            "max_new_tokens": mnew,
            "speedup": round(spec["toks_per_s"]
                             / max(1e-9, base["toks_per_s"]), 2),
            "base": base, "spec": spec,
            "bit_identical": True, "vs_baseline": None}


class _ThrottledKVTx:
    """Emulated cross-host NIC for the disagg TTFT A/B: occupy the
    sender for nbytes/rate before each frame enters the (instant,
    in-process) socketpair. Sender-side sleep is the right model —
    frames leave one at a time, and overlapped compute keeps running
    on other threads exactly as it would during real wire time."""

    def __init__(self, tx, mbps: float):
        self._tx = tx
        self._s_per_b = 1.0 / (mbps * 1e6)

    def send_handoff(self, msg):
        nb = sum(a.nbytes for a in msg if isinstance(a, np.ndarray))
        if nb:
            time.sleep(nb * self._s_per_b)
        return self._tx.send_handoff(msg)

    def __getattr__(self, name):
        return getattr(self._tx, name)


def bench_disagg_stream(wire_mbps=30.0, stream_chunk=64, plen=448,
                        seed=0):
    """Streamed prefill pages (ISSUE 19 tentpole): TTFT through the
    disaggregated gateway with chunked, streamed kvpage frames vs the
    all-at-completion handoff, over an emulated ``wire_mbps``
    cross-host interconnect (the in-process socketpair is effectively
    infinite bandwidth, which would hide exactly the serialization
    this feature removes). The streamed worker overlaps wire time
    with prefill compute and the feeder stages pages as they arrive,
    so first-token latency sheds most of the transfer. Gates: the
    streamed median TTFT is strictly below one-shot, and the token
    streams are bit-identical across both modes."""
    from mxtpu.models import llama
    from mxtpu.serve.gateway import Gateway
    from mxtpu.serve.gateway.disagg import DisaggBackend, KVChannel

    cfg = llama.LlamaConfig(vocab_size=2048, dim=512, n_layers=8,
                            n_heads=4, n_kv_heads=4, hidden_dim=1408,
                            max_seq_len=512, remat=False,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mnew, page = 4, 64
    kv_mb = (2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
             * plen * 4 / 1e6)

    def one_mode(sc):
        tx, rx = KVChannel.pair()
        be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1,
                           max_slots=2, max_len=512, min_bucket=64,
                           page_size=page, stream_chunk=sc,
                           channel=(_ThrottledKVTx(tx, wire_mbps), rx))
        gw = Gateway(backend=be, queue_max=16)
        rng = np.random.default_rng(seed)
        ttfts, toks = [], []
        try:
            h = gw.submit(rng.integers(0, cfg.vocab_size, plen), mnew,
                          seed=0, temperature=0.7)   # compile, untimed
            h.result(timeout=600)
            for i in range(5):
                h = gw.submit(rng.integers(0, cfg.vocab_size, plen),
                              mnew, seed=i + 1, temperature=0.7)
                toks.append([int(t) for t in h.result(timeout=600)])
                ttfts.append(1e3 * (h._first_at - h._submitted_at))
        finally:
            gw.close()
        return sorted(ttfts)[len(ttfts) // 2], toks

    ttft_one, toks_one = one_mode(0)
    ttft_stream, toks_stream = one_mode(stream_chunk)
    assert toks_stream == toks_one, \
        "streamed-prefill tokens diverged from the one-shot handoff"
    assert ttft_stream < ttft_one, (ttft_stream, ttft_one)
    return {"metric": "disagg_stream_ttft_ms",
            "value": round(ttft_stream, 1), "unit": "ms",
            "one_shot_ttft_ms": round(ttft_one, 1),
            "ttft_drop": round(1.0 - ttft_stream / ttft_one, 3),
            "emulated_wire_mbps": wire_mbps,
            "stream_chunk": stream_chunk, "page_size": page,
            "prompt_len": plen, "kv_mb": round(kv_mb, 1),
            "bit_identical": True, "vs_baseline": None}


def bench_gateway(n_requests=32, n_replicas=2, max_slots=8,
                  max_len=768, mean_interarrival_s=0.15, seed=0,
                  cfg=None):
    """Serving-TIER throughput + latency (ISSUE 6 tentpole): the same
    ~500M config served through the multi-replica HTTP gateway
    (``mxtpu.serve.gateway``) under a seeded OPEN-LOOP client stream —
    arrivals fire on the wall clock regardless of completion (the
    heavy-traffic regime: a closed loop would self-throttle and hide
    queueing). Reports tok/s over generated tokens plus client-side
    p50/p99 time-to-first-token AND inter-token latency — the two
    numbers a serving SLO is written against."""
    import threading as _threading
    from mxtpu.models import llama
    from mxtpu.serve import ServeEngine
    from mxtpu.serve.gateway import Gateway, GatewayClient

    cfg = cfg or llama.LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=8, n_heads=16,
        n_kv_heads=8, hidden_dim=5632, max_seq_len=max_len,
        remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    gw = Gateway(lambda: ServeEngine(cfg, params, max_slots=max_slots,
                                     max_len=max_len,
                                     min_bucket=max(4, max_len // 12)),
                 n_replicas=n_replicas, queue_max=max(64, n_requests))
    port = gw.start_http(port=0)
    plens = [max_len // 12, max_len // 6, max_len // 3, max_len // 2]
    try:
        # warmup: every prefill bucket + the decode program on EVERY
        # replica, outside the timed region (compile-then-measure
        # discipline). Sequential warmups would all land on the first
        # replica (least-loaded ties), so fire n_replicas CONCURRENT
        # requests per bucket — while one replica holds a live slot,
        # the router sends the next to a cold one.
        warm = []

        def _warm_one(prompt, j):
            warm.append(GatewayClient("127.0.0.1", port).generate(
                prompt, 8, seed=j))

        for bi, p in enumerate(plens):
            # prompts drawn on the main thread (rng is not thread-safe)
            prompts = [rng.integers(0, cfg.vocab_size, p)
                       for _ in range(n_replicas)]
            ts = [_threading.Thread(target=_warm_one,
                                    args=(prompts[k],
                                          bi * n_replicas + k))
                  for k in range(n_replicas)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        assert all(w["status"] == 200 for w in warm)

        jobs = []
        t_next = 0.0
        for i in range(n_requests):
            jobs.append(dict(
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.choice(plens))),
                mnew=int(rng.integers(8, max_len // 3 + 1)),
                at=t_next))
            t_next += float(rng.exponential(mean_interarrival_s))
        results = [None] * n_requests
        t0 = time.perf_counter()

        def fire(i, job):
            delay = t0 + job["at"] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            results[i] = GatewayClient("127.0.0.1", port).generate(
                job["prompt"], job["mnew"], seed=i)

        threads = [_threading.Thread(target=fire, args=(i, j))
                   for i, j in enumerate(jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
    finally:
        gw.close()
    ok = [r for r in results if r and r["status"] == 200]
    total_new = sum(len(r["tokens"]) for r in ok)
    ttfts = sorted(1e3 * (r["times"][0] - r["t0"])
                   for r in ok if r["times"])
    gaps = sorted(g for r in ok
                  for g in (1e3 * np.diff(r["times"])
                            if len(r["times"]) > 1 else []))

    def pct(xs, q):
        return round(float(xs[min(len(xs) - 1,
                                  int(q / 100 * len(xs)))]), 2) \
            if xs else 0.0

    return {"metric": "llama_500m_gateway_tokens_per_s",
            "value": round(total_new / dt, 1), "unit": "tok/s",
            "ttft_p50_ms": pct(ttfts, 50),
            "ttft_p99_ms": pct(ttfts, 99),
            "p50_token_ms": pct(gaps, 50),
            "p99_token_ms": pct(gaps, 99),
            "n_requests": n_requests, "n_ok": len(ok),
            "n_replicas": n_replicas, "max_slots": max_slots,
            "total_s": round(dt, 1), "vs_baseline": None}


# stdlib-only open-loop client (NO jax import: each swarm member is a
# REAL separate process, cheap to fork, talking plain HTTP/1.0 — the
# fleet bench's traffic must come from outside the server process or
# the GIL serializes client and server and the queueing story is
# fiction). argv: plan.json out.jsonl; the plan carries absolute
# firing offsets, every job runs on its own thread (open loop).
_FLEET_CLIENT_SRC = r"""
import json, socket, sys, threading, time
plan = json.load(open(sys.argv[1]))
host, port = plan["host"], plan["port"]
out = open(sys.argv[2], "w")
lock = threading.Lock()
t0 = time.perf_counter()

def fire(job):
    delay = t0 + job["at"] - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    body = json.dumps({
        "prompt": job["prompt"], "max_new_tokens": job["mnew"],
        "temperature": job["temperature"], "seed": job["seed"],
        "model": job["model"], "priority": job["priority"],
        "session_id": job.get("session_id"), "stream": True}).encode()
    rec = {"id": job["id"], "model": job["model"],
           "priority": job["priority"], "seed": job["seed"],
           "status": 0, "tokens": [], "reason": None,
           "version": None, "ttft_ms": None}
    try:
        s = socket.create_connection((host, port), timeout=600)
        t_send = time.perf_counter()
        s.sendall(("POST /v1/generate HTTP/1.0\r\nHost: x\r\n"
                   "Content-Length: %d\r\n"
                   "Content-Type: application/json\r\n\r\n"
                   % len(body)).encode() + body)
        f = s.makefile("rb")
        rec["status"] = int(f.readline().split()[1])
        while f.readline().strip():
            pass
        if rec["status"] == 200:
            for line in f:
                evt = json.loads(line)
                if evt.get("done"):
                    rec["reason"] = evt.get("reason")
                    rec["tokens"] = evt["tokens"]
                    rec["version"] = evt.get("version")
                    break
                if rec["ttft_ms"] is None:
                    rec["ttft_ms"] = 1e3 * (time.perf_counter()
                                            - t_send)
        f.close(); s.close()
    except Exception as e:
        rec["error"] = repr(e)
    with lock:
        out.write(json.dumps(rec) + "\n")
        out.flush()

threads = [threading.Thread(target=fire, args=(j,))
           for j in plan["jobs"]]
for t in threads:
    t.start()
for t in threads:
    t.join()
out.close()
print("done", flush=True)
"""


def bench_fleet(seed=0, n_chat=44, chat_mnew=48, n_clients=3):
    """Fleet control plane end to end (ISSUE 15 acceptance gate): two
    tiny models behind ONE front door, hammered by a seeded Poisson
    swarm of separate client PROCESSES with mixed priorities and
    sessions, while a :class:`ServeChaosPlan` kills a replica and a
    live checkpoint hot-swap replaces one model's weights mid-run.
    Gated on the federated /metrics scrape:

    - every completed request's tokens are bit-identical to a
      per-request ``llama.generate`` with the weights of the BUILD
      the response is labelled with (chaos kill and hot-swap
      included);
    - the arbiter demonstrably moves >= 1 chip from the idle model to
      the burning one (``fleet_scale_events_total`` both directions)
      and the hot model's SLO is not breached once the queue drains;
    - batch traffic is shed first: ``gateway_shed_total`` has batch
      sheds and ZERO interactive sheds, and interactive p99 TTFT
      stays inside the SLO target through the burn."""
    import os
    import subprocess
    import tempfile
    import threading as _threading
    from dataclasses import replace as _replace
    from mxtpu import telemetry as tm
    from mxtpu.contrib.chaos import ServeChaosPlan, attach_serve
    from mxtpu.models import llama
    from mxtpu.serve import ServeEngine
    from mxtpu.serve.fleet import ArbiterPolicy, FleetGateway, ModelSpec
    from mxtpu.serve.gateway import GatewayClient
    from mxtpu.telemetry import parse_prometheus

    cfg = _replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                   remat=False, attn_impl="dense", max_seq_len=64)
    p_chat = llama.init_params(cfg, jax.random.PRNGKey(0))
    p_chat_v1 = llama.init_params(cfg, jax.random.PRNGKey(1))
    p_embed = llama.init_params(cfg, jax.random.PRNGKey(2))
    by_build = {("chat", "v0"): p_chat, ("chat", "v1"): p_chat_v1,
                ("embed", "v0"): p_embed}
    rng = np.random.default_rng(seed)
    plen, temp = 6, 0.7

    def fac(params0):
        return lambda params=params0: ServeEngine(
            cfg, params, max_slots=2, max_len=64, min_bucket=8)

    # batch sees 15% of the queue bound: the burst is sized so batch
    # HITS its bound while interactive never reaches the full one —
    # the shed-ordering assertion is then deterministic given arrival
    # order, not CPU speed
    os.environ["MXTPU_FLEET_BATCH_QUEUE_FRAC"] = "0.15"
    peer_reg = tm.MetricsRegistry()
    peer_reg.counter("fleet_bench_clients_total",
                     "swarm driver federation probe").inc(n_clients)
    peer = tm.RegistryServer(port=0, registry=peer_reg,
                             process="swarm")
    fleet = FleetGateway(
        [ModelSpec("chat", fac(p_chat), replicas=1, min_replicas=1,
                   max_replicas=2, slo={"ttft_ms": 30000.0}),
         ModelSpec("embed", fac(p_embed), replicas=2, min_replicas=1,
                   max_replicas=2)],
        arbiter=ArbiterPolicy(chip_budget=3, interval_s=0.25,
                              cooldown_s=1.0, pressure_high=1.5,
                              occupancy_low=0.35, idle_s=0.8),
        queue_max=64, federate=[("127.0.0.1", peer.port)])
    chaos = attach_serve(fleet.pool("embed"),
                         ServeChaosPlan(seed=seed,
                                        kill_replica={0: 8}))
    port = fleet.start_http(port=0)
    reg = tm.registry()

    def mkprompt():
        return [int(t) for t in rng.integers(0, cfg.vocab_size, plen)]

    tmp = tempfile.mkdtemp(prefix="mxtpu_fleet_")
    try:
        # warmup: the one prefill bucket + decode on every replica of
        # both pools, outside the timed region (concurrent per pool so
        # the least-loaded router spreads to cold replicas)
        warm = []

        def _warm(model, j):
            warm.append(GatewayClient("127.0.0.1", port).generate(
                mkprompt(), 4, seed=100 + j, temperature=temp,
                model=model))

        ws = [_threading.Thread(target=_warm, args=(m, j))
              for j, m in enumerate(("chat", "embed", "embed"))]
        for t in ws:
            t.start()
        for t in ws:
            t.join()
        assert all(w["status"] == 200 for w in warm), warm

        # the swarm plan: 4 embed requests then silence (the pool must
        # go SUSTAINED-idle to become the donor), and a chat burst far
        # above service rate (arrivals ~70/s): queue pressure is then
        # guaranteed by arithmetic, not CPU timing
        jobs = []
        for i in range(4):
            jobs.append(dict(id=len(jobs), model="embed",
                             prompt=mkprompt(), mnew=16,
                             temperature=temp, seed=len(jobs),
                             priority="interactive",
                             session_id=f"e{i % 2}",
                             at=round(0.1 * i, 3)))
        t_at = 0.3
        for i in range(n_chat):
            t_at += float(rng.exponential(0.013))
            jobs.append(dict(id=len(jobs), model="chat",
                             prompt=mkprompt(), mnew=chat_mnew,
                             temperature=temp, seed=len(jobs),
                             priority=("interactive" if i % 2 == 0
                                       else "batch"),
                             session_id=(f"s{i % 6}" if i % 2 == 0
                                         else None),
                             at=round(t_at, 3)))
        procs, outs = [], []
        for c in range(n_clients):
            pf = os.path.join(tmp, f"plan{c}.json")
            of = os.path.join(tmp, f"out{c}.jsonl")
            with open(pf, "w") as fh:
                json.dump({"host": "127.0.0.1", "port": port,
                           "jobs": jobs[c::n_clients]}, fh)
            outs.append(of)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _FLEET_CLIENT_SRC, pf, of],
                stdout=subprocess.PIPE, text=True))
        t0 = time.perf_counter()
        fleet.metrics_text()        # opens the goodput window

        # wait for the chip MOVE (embed sustained-idle donates, chat
        # burning claims), then for the queue to subside, then swap
        # chat's weights LIVE while stragglers are still in flight
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if reg.value("fleet_scale_events_total", model="chat",
                         direction="up") >= 1:
                break
            time.sleep(0.1)
        else:
            raise AssertionError(
                "arbiter never granted the burning pool a chip: "
                f"{fleet.arbiter.describe()}")
        while (fleet.pool("chat").load_total()["queued"] > 4
               and time.monotonic() < deadline):
            time.sleep(0.1)
        swap = fleet.hot_swap("chat", params=p_chat_v1)
        assert swap["version"] == "v1", swap

        # post-swap verification traffic: same sessions, new build
        post = []
        post_prompts = [mkprompt() for _ in range(8)]

        def _post(j):
            rec = GatewayClient(
                "127.0.0.1", port, timeout=600).generate(
                    post_prompts[j], 16, seed=500 + j,
                    temperature=temp, model="chat",
                    priority="interactive", session_id=f"s{j % 6}")
            post.append((j, rec))

        ps = [_threading.Thread(target=_post, args=(j,))
              for j in range(8)]
        for t in ps:
            t.start()
        for t in ps:
            t.join()
        for p in procs:
            assert p.wait(timeout=600) == 0
        dt = time.perf_counter() - t0
        results = [json.loads(l) for of in outs
                   for l in open(of)]
    finally:
        text = fleet.metrics_text()
        fleet.close()
        peer.close()
        os.environ.pop("MXTPU_FLEET_BATCH_QUEUE_FRAC", None)

    # -- gate 1: bit-identity, per BUILD, chaos + swap included ---------
    jmap = {j["id"]: j for j in jobs}
    refs = {}

    def ref(model, version, prompt, mnew, seed_):
        key = (model, version, mnew)
        if key not in refs:
            refs[key] = jax.jit(lambda p, pr, r: llama.generate(
                cfg, p, pr, mnew, temperature=temp, rng=r))
        out = refs[key](by_build[(model, version)],
                        jnp.asarray(prompt, jnp.int32)[None],
                        jax.random.PRNGKey(seed_))
        return [int(t) for t in np.asarray(out)[0, len(prompt):]]

    done = [r for r in results if r["status"] == 200]
    for r in done:
        j = jmap[r["id"]]
        want = ref(r["model"], r["version"], j["prompt"], j["mnew"],
                   r["seed"])
        assert r["tokens"] == want[:len(r["tokens"])], (
            f"divergence on job {r['id']} "
            f"({r['model']}@{r['version']}): {r['tokens']} != {want}")
    for j, r in post:
        assert r["status"] == 200 and r["version"] == "v1", r
        want = ref("chat", "v1", post_prompts[j], 16, 500 + j)
        assert r["tokens"] == want[:len(r["tokens"])], (j, r, want)
    total_new = sum(len(r["tokens"]) for r in done)
    assert chaos.injected["replica_kill"] == 1, chaos.injected
    assert len([r for r in done if r["model"] == "embed"]) >= 1
    assert len(done) >= 10, f"only {len(done)} completed"

    # -- gate 2+3: federated scrape carries the whole story -------------
    parsed = parse_prometheus(text)
    s = parsed["samples"]

    def sval(name, **labels):
        return s.get((name, tuple(sorted(labels.items()))), 0.0)

    assert sval("mxtpu_fleet_scale_events_total", model="chat",
                direction="up") >= 1, s
    assert sval("mxtpu_fleet_scale_events_total", model="embed",
                direction="down") >= 1, s
    assert sval("mxtpu_fleet_swap_total", model="chat") >= 1
    assert sval("mxtpu_fleet_bench_clients_total",
                process="swarm") == n_clients, "federation broken"
    # the aggregate series only: federation ALSO exports every sample
    # per-process, and summing both would double-count
    batch_shed = sum(v for (n, lab), v in s.items()
                     if n == "mxtpu_gateway_shed_total"
                     and dict(lab).get("priority") == "batch"
                     and "process" not in dict(lab))
    inter_shed = sum(v for (n, lab), v in s.items()
                     if n == "mxtpu_gateway_shed_total"
                     and dict(lab).get("priority") == "interactive"
                     and "process" not in dict(lab))
    assert batch_shed > 0, "burst never shed batch traffic"
    assert inter_shed == 0, f"{inter_shed} interactive sheds"
    assert ("mxtpu_goodput_ratio", (("loop", "fleet"),)) in s
    assert not fleet.gateway("chat").slo.breached, \
        "chat SLO still burning after the chip grant"

    ttfts = sorted(r["ttft_ms"] for r in done
                   if r["priority"] == "interactive"
                   and r["ttft_ms"] is not None)
    p99 = ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))] \
        if ttfts else 0.0
    assert p99 < 30000.0, f"interactive p99 TTFT {p99}ms out of SLO"
    n429 = len([r for r in results if r["status"] == 429])
    # returning-session TTFT (ISSUE 19): every post-swap request
    # reuses a session the swarm already ran, so session + prefix
    # affinity route it back to the replica that served it — this is
    # the quiet-fleet TTFT a returning user sees, reported next to
    # the under-burn p99 above
    ret = sorted(1e3 * (r["times"][0] - r["t0"])
                 for _, r in post if r["times"])
    ret_p50 = round(ret[len(ret) // 2], 1) if ret else 0.0
    return {"metric": "fleet_gateway_tokens_per_s",
            "value": round(total_new / dt, 1), "unit": "tok/s",
            "n_jobs": len(jobs), "n_ok": len(done), "n_shed": n429,
            "batch_shed": int(batch_shed),
            "interactive_ttft_p99_ms": round(p99, 1),
            "returning_session_ttft_p50_ms": ret_p50,
            "scale_up_chat": int(sval("mxtpu_fleet_scale_events_total",
                                      model="chat", direction="up")),
            "scale_down_embed": int(sval(
                "mxtpu_fleet_scale_events_total", model="embed",
                direction="down")),
            "swap": swap, "chaos_injected": dict(chaos.injected),
            "n_clients": n_clients, "total_s": round(dt, 1),
            "vs_baseline": None}


def _on_cpu_mesh(impl_fn_name: str, n: int = 8):
    """Run ``bench.<impl_fn_name>()`` on an n-device virtual CPU mesh:
    directly when this process already is one, else via re-exec (same
    recipe as __graft_entry__.dryrun_multichip), parsing the repr the
    child prints as its last line."""
    if len(jax.devices()) >= n and jax.default_backend() == "cpu":
        return globals()[impl_fn_name]()
    import ast
    from __graft_entry__ import respawn_on_cpu_mesh
    out = respawn_on_cpu_mesh(
        n, f"import bench; print(bench.{impl_fn_name}())\n",
        capture=True)
    return ast.literal_eval(out.strip().splitlines()[-1])


def bench_aot8b():
    """AOT lower+compile of the FULL llama3_8b sharded train step on
    an 8-device virtual CPU mesh (VERDICT r2 #2): measures trace+lower
    wall time, StableHLO size, compile time, and per-device sharded
    state bytes."""
    return _on_cpu_mesh("_aot8b_impl")


# -- shared AOT scaffolding (one copy: all three gates must build the
# abstract sharded state the same way or they'd measure different
# things) ----------------------------------------------------------------
def _abs_sharded_params(cfg, mesh, builder=None, rules=None):
    """eval_shape'd params with rule-table NamedShardings attached —
    the ONE recipe every AOT gate builds its abstract tree with
    (pass builder/rules for non-default trees, e.g. the int8 gate)."""
    from mxtpu.models import llama
    rules = rules if rules is not None else llama.sharding_rules(cfg)
    builder = builder or (lambda: llama.init_params(cfg))
    from jax.sharding import NamedSharding
    abs_p = jax.eval_shape(builder)
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
        abs_p, rules.tree_specs(abs_p),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)), rules


def _abs_train_args(cfg, mesh, tx, batch_rows, seq):
    """Abstract (TrainState, batch) for a sharded llama train step."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.parallel import step as pstep
    abs_params, rules = _abs_sharded_params(cfg, mesh)
    abs_opt = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        jax.eval_shape(tx.init, abs_params),
        pstep.opt_state_shardings(tx, abs_params, mesh, rules))
    abs_state = pstep.TrainState(
        abs_params, abs_opt,
        jax.ShapeDtypeStruct((), jnp.int32,
                             sharding=NamedSharding(mesh, P())), ())
    abs_batch = {"tokens": jax.ShapeDtypeStruct(
        (batch_rows, seq), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"))))}
    return abs_state, abs_batch, rules


def _abs_decode_args(cfg, mesh, batch, ctx):
    """Abstract (params, token, cache) for a sharded decode step."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.models import llama
    abs_params, _ = _abs_sharded_params(cfg, mesh)
    abs_cache = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
        jax.eval_shape(lambda: llama.init_cache(cfg, batch, ctx)),
        llama.cache_specs(cfg, mesh, batch))
    abs_tok = jax.ShapeDtypeStruct(
        (batch, 1), jnp.int32, sharding=NamedSharding(mesh, P()))
    return abs_params, abs_tok, abs_cache


def _aot8b_impl():
    import optax
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep

    cfg = llama.CONFIGS["llama3_8b"]
    mesh = pmesh.create_mesh(dp=1, fsdp=4, tp=2)
    tx = optax.adamw(1e-4)
    t0 = time.perf_counter()
    abs_state, abs_batch, rules = _abs_train_args(
        cfg, mesh, tx, 4, cfg.max_seq_len)
    step = pstep.make_train_step(llama.loss_fn(cfg), tx, mesh, rules)
    lowered = step._jitted.lower(abs_state, abs_batch, None)
    t_lower = time.perf_counter() - t0
    hlo_mb = len(lowered.as_text()) / 1e6
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t1
    from mxtpu.telemetry import perfscope
    costs = perfscope.program_costs(compiled, name="aot8b_train_step",
                                    spec=perfscope.spec_for("v5e"))
    state_gb = costs["argument_bytes"] / 1e9
    return {"metric": "llama3_8b_aot_state_gb_per_device",
            "value": round(state_gb, 2), "unit": "GB",
            "lower_s": round(t_lower, 1), "hlo_mb": round(hlo_mb, 2),
            "compile_s": round(t_compile, 1),
            "flops": costs["flops"],
            "bytes_accessed": costs["bytes_accessed"],
            "roofline": costs["roofline"],
            "mesh": "dp1_fsdp4_tp2_x8", "vs_baseline": None}


def bench_aot8b_decode():
    """AOT lower+compile of sharded llama3_8b DECODE (VERDICT r3 #1):
    the serving half of the flagship. Self-provisions the 8-device
    virtual CPU mesh like bench_aot8b."""
    return _on_cpu_mesh("_aot8b_decode_impl")


def _aot8b_decode_impl(batch=8, prefill_len=2048):
    """Serving layout: pure tp=8 (the Megatron inference layout — no
    fsdp weight all-gather inside the latency-critical decode step),
    bf16 weights, KV cache sharded on the kv-head axis (8 kv heads, 1
    per device) at the full 8k context. One chip cannot serve this
    model at all — bf16 weights alone are 16GB, the whole v5e HBM —
    so the gates below are the per-device sharded-memory story."""
    from dataclasses import replace
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh

    cfg = replace(llama.CONFIGS["llama3_8b"],
                  param_dtype=jnp.bfloat16)
    mesh = pmesh.create_mesh(tp=8)
    ctx = cfg.max_seq_len
    t0 = time.perf_counter()
    abs_params, abs_tok, abs_cache = _abs_decode_args(
        cfg, mesh, batch, ctx)
    # the cache is donated: decode must update it in place in HBM, not
    # hold two 8k-context caches during the step
    step = jax.jit(partial(llama.decode_step, cfg, mesh=mesh),
                   donate_argnums=(2,))
    lowered = step.lower(abs_params, abs_tok, abs_cache)
    t_lower = time.perf_counter() - t0
    hlo_mb = len(lowered.as_text()) / 1e6
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t1
    from mxtpu.telemetry import perfscope
    costs = perfscope.program_costs(compiled, name="aot8b_decode",
                                    spec=perfscope.spec_for("v5e"))
    # argument/peak sizes are per-device; temp_size on this backend is
    # whole-host across all partitions (the r3-gated train step shows
    # temp=79GB with peak=args=12.05GB), so peak is the honest HBM gate
    args_gb = costs["argument_bytes"] / 1e9
    peak_gb = costs["peak_hbm_bytes"] / 1e9

    # prefill for the same cache layout (chunked prompts re-enter it)
    abs_prompt = jax.ShapeDtypeStruct(
        (batch, prefill_len), jnp.int32,
        sharding=NamedSharding(mesh, P()))
    pf = jax.jit(partial(llama.prefill, cfg, mesh=mesh,
                         last_only=True),
                 donate_argnums=(2,))
    t2 = time.perf_counter()
    pf_compiled = pf.lower(abs_params, abs_prompt, abs_cache).compile()
    t_pf = time.perf_counter() - t2
    pf_costs = perfscope.program_costs(
        pf_compiled, name="aot8b_prefill",
        spec=perfscope.spec_for("v5e"))
    pf_peak_gb = pf_costs["peak_hbm_bytes"] / 1e9
    return {"metric": "llama3_8b_decode_args_gb_per_device",
            "value": round(args_gb, 2), "unit": "GB",
            "lower_s": round(t_lower, 1), "hlo_mb": round(hlo_mb, 2),
            "compile_s": round(t_compile, 1),
            "peak_gb": round(peak_gb, 2),
            "flops": costs["flops"],
            "bytes_accessed": costs["bytes_accessed"],
            "roofline": costs["roofline"],
            "prefill_compile_s": round(t_pf, 1),
            "prefill_peak_gb": round(pf_peak_gb, 2),
            "batch": batch, "ctx": ctx, "mesh": "tp8_bf16",
            "vs_baseline": None}


def bench_aot8b_int8():
    """AOT lower+compile of weight-only int8 llama3_8b decode on the
    tp8 serving mesh (VERDICT r4 #4): halves the per-device weight
    bytes of the bf16 gate."""
    return _on_cpu_mesh("_aot8b_int8_impl")


def _aot8b_int8_impl(batch=8):
    """Same layout as _aot8b_decode_impl (pure tp8, kv-head-sharded
    donated cache, full 8k context) with the weights weight-only int8
    (quantize_params_int8 / int8_sharding_rules): 16.06 GB bf16 →
    8.06 GB int8 (+32 MB scales), so args/device drop from ~3.08 GB
    to ~2.08 GB — the headroom is 2× context or tp4 serving."""
    from dataclasses import replace
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh

    cfg = replace(llama.CONFIGS["llama3_8b"],
                  param_dtype=jnp.bfloat16)
    mesh = pmesh.create_mesh(tp=8)
    ctx = cfg.max_seq_len
    t0 = time.perf_counter()
    abs_q, _ = _abs_sharded_params(
        cfg, mesh,
        builder=lambda: llama.quantize_params_int8(
            cfg, llama.init_params(cfg)),
        rules=llama.int8_sharding_rules(cfg))
    _, abs_tok, abs_cache = _abs_decode_args(cfg, mesh, batch, ctx)
    step = jax.jit(partial(llama.decode_step, cfg, mesh=mesh),
                   donate_argnums=(2,))
    lowered = step.lower(abs_q, abs_tok, abs_cache)
    t_lower = time.perf_counter() - t0
    hlo_mb = len(lowered.as_text()) / 1e6
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t1
    from mxtpu.telemetry import perfscope
    costs = perfscope.program_costs(compiled, name="aot8b_int8_decode",
                                    spec=perfscope.spec_for("v5e"))
    args_gb = costs["argument_bytes"] / 1e9
    peak_gb = costs["peak_hbm_bytes"] / 1e9
    return {"metric": "llama3_8b_int8_decode_args_gb_per_device",
            "value": round(args_gb, 2), "unit": "GB",
            "lower_s": round(t_lower, 1), "hlo_mb": round(hlo_mb, 2),
            "compile_s": round(t_compile, 1),
            "peak_gb": round(peak_gb, 2),
            "flops": costs["flops"],
            "bytes_accessed": costs["bytes_accessed"],
            "roofline": costs["roofline"],
            "batch": batch, "ctx": ctx, "mesh": "tp8_int8",
            "vs_baseline": None}


def bench_aot8b_32k():
    """AOT lower+compile of llama3_8b LONG-CONTEXT serving: 32k
    context on the tp8 mesh via chunked (streaming) prefill + decode
    (VERDICT r4 #5)."""
    return _on_cpu_mesh("_aot8b_32k_impl")


def _aot8b_32k_impl(batch=8, ctx=32768, chunk=1024):
    """32k-context serving feasibility. Single-shot prefill at 32k
    materializes per-layer (b, h, s, ctx) f32 attention logits —
    ~1 TB, uncompilable — so the prefill half gates
    ``llama.chunked_prefill`` (peak scales with the chunk). Cache at
    32k: 2·32·8·8·32768·128·2B = 34.36 GB → 4.29 GB/device on tp8;
    with bf16 weights (2.01) the decode args are ~6.3 GB/device on a
    16 GB v5e."""
    from dataclasses import replace
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh

    cfg = replace(llama.CONFIGS["llama3_8b"],
                  param_dtype=jnp.bfloat16, max_seq_len=ctx)
    mesh = pmesh.create_mesh(tp=8)
    t0 = time.perf_counter()
    abs_params, abs_tok, abs_cache = _abs_decode_args(
        cfg, mesh, batch, ctx)
    step = jax.jit(partial(llama.decode_step, cfg, mesh=mesh),
                   donate_argnums=(2,))
    compiled = step.lower(abs_params, abs_tok, abs_cache).compile()
    from mxtpu.telemetry import perfscope
    costs = perfscope.program_costs(compiled, name="aot8b_32k_decode",
                                    spec=perfscope.spec_for("v5e"))
    args_gb = costs["argument_bytes"] / 1e9
    peak_gb = costs["peak_hbm_bytes"] / 1e9

    # chunked prefill of a 30k prompt into the 32k cache (the last 2k
    # is generation headroom); scan keeps the HLO O(1) in chunk count
    abs_prompt = jax.ShapeDtypeStruct(
        (batch, ctx - 2048), jnp.int32,
        sharding=NamedSharding(mesh, P()))
    pf = jax.jit(partial(llama.chunked_prefill, cfg,
                         chunk_size=chunk, mesh=mesh),
                 donate_argnums=(2,))
    t1 = time.perf_counter()
    lowered = pf.lower(abs_params, abs_prompt, abs_cache)
    hlo_mb = len(lowered.as_text()) / 1e6
    pf_compiled = lowered.compile()
    t_pf = time.perf_counter() - t1
    pf_costs = perfscope.program_costs(
        pf_compiled, name="aot8b_32k_prefill",
        spec=perfscope.spec_for("v5e"))
    pf_peak_gb = pf_costs["peak_hbm_bytes"] / 1e9
    return {"metric": "llama3_8b_32k_decode_args_gb_per_device",
            "value": round(args_gb, 2), "unit": "GB",
            "peak_gb": round(peak_gb, 2),
            "flops": costs["flops"],
            "bytes_accessed": costs["bytes_accessed"],
            "roofline": costs["roofline"],
            "prefill_peak_gb": round(pf_peak_gb, 2),
            "prefill_compile_s": round(t_pf, 1),
            "hlo_mb": round(hlo_mb, 2),
            "total_s": round(time.perf_counter() - t0, 1),
            "batch": batch, "ctx": ctx, "chunk": chunk,
            "mesh": "tp8_bf16", "vs_baseline": None}


def bench_aot_moe():
    """AOT lower+compile of the Mixtral-8x7B-class MoE train step AND
    its tp8 serving decode (expert parallelism at scale): the 46.7B
    sparse flagship on an 8-device virtual CPU mesh."""
    return _on_cpu_mesh("_aot_moe_impl")


def _aot_moe_impl(batch=4, seq=2048):
    """Train: dp1×fsdp2×ep2×tp2 (expert banks over ep AND fsdp/tp per
    expert). Serving: pure tp8, bf16 weights, dense-mixture experts.
    Like the 8B gates, no weights materialize — eval_shape +
    NamedShardings; the numbers are the per-device feasibility story
    for a 46.7B sparse model."""
    from dataclasses import replace
    from functools import partial
    import optax
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep

    cfg = replace(llama.CONFIGS["mixtral_8x7b"], max_seq_len=seq)
    mesh = pmesh.create_mesh(dp=1, fsdp=2, ep=2, tp=2)
    tx = optax.adamw(1e-4)
    t0 = time.perf_counter()
    abs_state, abs_batch, rules = _abs_train_args(cfg, mesh, tx,
                                                  batch, seq)
    n_params = sum(x.size for x in jax.tree.leaves(abs_state.params))
    step = pstep.make_train_step(llama.loss_fn(cfg, mesh), tx, mesh,
                                 rules)
    lowered = step._jitted.lower(abs_state, abs_batch, None)
    t_lower = time.perf_counter() - t0
    hlo_mb = len(lowered.as_text()) / 1e6
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t1
    from mxtpu.telemetry import perfscope
    costs = perfscope.program_costs(compiled, name="aot_moe_train_step",
                                    spec=perfscope.spec_for("v5e"))
    train_gb = costs["argument_bytes"] / 1e9
    train_peak = costs["peak_hbm_bytes"] / 1e9

    # serving: bf16, pure tp8, dense-mixture experts, donated cache
    scfg = replace(cfg, param_dtype=jnp.bfloat16)
    smesh = pmesh.create_mesh(tp=8)
    abs_sp, abs_tok, abs_cache = _abs_decode_args(scfg, smesh, 8, seq)
    dstep = jax.jit(partial(llama.decode_step, scfg, mesh=smesh),
                    donate_argnums=(2,))
    t2 = time.perf_counter()
    dc = dstep.lower(abs_sp, abs_tok, abs_cache).compile()
    t_dec = time.perf_counter() - t2
    dcosts = perfscope.program_costs(dc, name="aot_moe_decode",
                                     spec=perfscope.spec_for("v5e"))
    return {"metric": "mixtral8x7b_aot_train_state_gb_per_device",
            "value": round(train_gb, 2), "unit": "GB",
            "n_params_b": round(n_params / 1e9, 2),
            "lower_s": round(t_lower, 1), "hlo_mb": round(hlo_mb, 2),
            "compile_s": round(t_compile, 1),
            "train_peak_gb": round(train_peak, 2),
            "flops": costs["flops"],
            "bytes_accessed": costs["bytes_accessed"],
            "roofline": costs["roofline"],
            "decode_args_gb": round(
                dcosts["argument_bytes"] / 1e9, 2),
            "decode_peak_gb": round(dcosts["peak_hbm_bytes"] / 1e9, 2),
            "decode_compile_s": round(t_dec, 1),
            "train_mesh": "dp1_fsdp2_ep2_tp2",
            "decode_mesh": "tp8_bf16", "vs_baseline": None}


def bench_input_pipeline():
    """Host decode capacity of the native input pipeline: RecordIO
    read, JPEG decode and the raw C++ pipeline, none of which touch
    jax. Runs ``benchmark/input_bench.py --host-only`` in a child
    pinned to the CPU backend — this process may hold the chip, and a
    chip belongs to one process. The legs that upload to the device
    are ``input_bench.py`` run as a process of its own. A failed child
    is a failed bench."""
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(here, "benchmark", "input_bench.py"),
         "--n", "300", "--seconds", "1.5", "--host-only"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(
            f"input_bench.py exited {proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.splitlines()[-1])


def _smoke_llama_cfg():
    """The one tiny CPU-safe config shared by bench_smoke_run and the
    perf gate's smoke path — a single definition so the two CI stages
    cannot drift onto different models."""
    from mxtpu.models import llama
    return llama.LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        hidden_dim=128, max_seq_len=64, attn_impl="blockwise")


def bench_smoke_run():
    """One REAL train step on a tiny llama config — CI's bench-path
    regression check (a jit/shape break here fails bench_smoke)."""
    t_s, mfu, n_p = bench_llama(batch=2, seq=64, steps=2,
                                cfg=_smoke_llama_cfg())
    return {"metric": "smoke_llama_tokens_per_s", "value": round(t_s, 1),
            "unit": "tok/s", "mfu": round(mfu, 4), "n_params": n_p,
            "vs_baseline": 1.0}


# ---------------------------------------------------------------------------
# whole-model perf regression gate (VERDICT r5 #5): per-config
# step-time/MFU vs the committed benchmark/baseline_models.json.
# The model-level analogue of benchmark/opperf's latency gate —
# a remat/sharding/lowering regression in any flagship step must fail
# CI loudly instead of surfacing as a silent BENCH_rNN diff.
# ---------------------------------------------------------------------------
BASELINE_MODELS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "benchmark", "baseline_models.json")


def _gate_resnet(stem):
    img_s, mfu, _ = bench_resnet(stem=stem)
    return {"step_ms": round(256 / img_s * 1000, 2), "mfu": round(mfu, 3),
            "throughput": round(img_s, 1), "unit": "img/s", "batch": 256}


def _gate_bert():
    s_s, mfu = bench_bert()
    return {"step_ms": round(128 / s_s * 1000, 2), "mfu": round(mfu, 3),
            "throughput": round(s_s, 1), "unit": "samples/s", "batch": 128}


def _gate_llama():
    t_s, mfu, _ = bench_llama()
    return {"step_ms": round(4 * 2048 / t_s * 1000, 2),
            "mfu": round(mfu, 3), "throughput": round(t_s, 1),
            "unit": "tok/s", "batch": 4}


def _gate_llama_decode(int8=False):
    """Decode tok/s, gated (ISSUE 4 satellite: BENCH_r05 showed decode
    reporting vs_baseline: null — a decode regression could land
    silently). step_ms is the whole timed generate call (batch 32 ×
    256 new tokens)."""
    d_s = bench_llama_decode(int8=int8)
    return {"step_ms": round(32 * 256 / d_s * 1000, 2),
            "throughput": round(d_s, 1), "unit": "tok/s", "batch": 32}


def _gate_llama_serve():
    """Continuous-batching serve: step_ms is the mean decode-step
    wall time under the seeded Poisson stream; throughput/latency ride
    along for the BENCH record."""
    rec = bench_llama_serve()
    return {"step_ms": round(1000.0 * rec["total_s"]
                             / max(rec["steps"], 1), 2),
            "throughput": rec["value"], "unit": "tok/s",
            "p50_token_ms": rec["p50_token_ms"],
            "p99_token_ms": rec["p99_token_ms"],
            "batch": rec["max_slots"]}


def _gate_gateway():
    """Serving-tier gate: step_ms is mean ms per generated token
    through the gateway under the seeded open-loop stream; TTFT and
    inter-token percentiles ride along for the BENCH record."""
    rec = bench_gateway()
    total_new = max(1, round(rec["value"] * rec["total_s"]))
    return {"step_ms": round(1000.0 * rec["total_s"] / total_new, 3),
            "throughput": rec["value"], "unit": "tok/s",
            "ttft_p50_ms": rec["ttft_p50_ms"],
            "ttft_p99_ms": rec["ttft_p99_ms"],
            "p50_token_ms": rec["p50_token_ms"],
            "p99_token_ms": rec["p99_token_ms"],
            "batch": rec["max_slots"] * rec["n_replicas"]}


def _gate_smoke_llama():
    """CPU-safe tiny config — exercises the same measurement path so
    the gate plumbing is testable without a chip. Batch 8 so the dp
    mesh divides on any 1/2/4/8-device box (the tier-1 gate test runs
    under the suite's 8-virtual-device XLA_FLAGS)."""
    t_s, mfu, _ = bench_llama(batch=8, seq=64, steps=6,
                              cfg=_smoke_llama_cfg())
    return {"step_ms": round(8 * 64 / t_s * 1000, 2),
            "mfu": round(mfu, 4), "throughput": round(t_s, 1),
            "unit": "tok/s", "batch": 8}


GATE_CONFIGS = {
    "resnet50": lambda: _gate_resnet("std"),
    "resnet50_s2d": lambda: _gate_resnet("s2d"),
    "bert_base": _gate_bert,
    "llama_509m": _gate_llama,
    "llama_509m_decode": _gate_llama_decode,
    "llama_509m_decode_int8": lambda: _gate_llama_decode(int8=True),
    "llama_509m_serve": _gate_llama_serve,
    "llama_509m_gateway": _gate_gateway,
    "smoke_llama": _gate_smoke_llama,
}


def _gate_injections():
    """MXTPU_BENCH_INJECT='name:factor,...' multiplies the measured
    step_ms — the gate's seeded-regression hook (tests/test_bench_gate
    .py), mirroring MXTPU_OPPERF_INJECT."""
    out = {}
    for part in os.environ.get("MXTPU_BENCH_INJECT", "").split(","):
        if ":" in part:
            name, factor = part.rsplit(":", 1)
            out[name.strip()] = float(factor)
    return out


def gate_measure(names):
    inject = _gate_injections()
    recs = {}
    for name in names:
        if name not in GATE_CONFIGS:
            raise SystemExit(f"unknown gate config {name!r}; have "
                             f"{sorted(GATE_CONFIGS)}")
        rec = GATE_CONFIGS[name]()
        if name in inject:
            rec["step_ms"] = round(rec["step_ms"] * inject[name], 2)
            rec["injected"] = inject[name]
        recs[name] = rec
    return recs


def gate_compare(baseline, current, tolerance):
    """Pure compare: every baseline config must be present and within
    ``tolerance × baseline step_ms``. Returns (violations, lines);
    faster-than-baseline is reported (re-baseline nudge) but passes."""
    violations, lines = [], []
    for name, base in sorted(baseline.items()):
        cur = current.get(name)
        if cur is None:
            violations.append(name)
            lines.append(f"MISSING {name}: in baseline but not in this "
                         f"run (the baseline is a contract)")
            continue
        ratio = cur["step_ms"] / base["step_ms"]
        if ratio > tolerance:
            violations.append(name)
            lines.append(
                f"REGRESSION {name}: {cur['step_ms']:.2f} ms/step vs "
                f"baseline {base['step_ms']:.2f} ({ratio:.2f}x > "
                f"{tolerance:.2f}x)")
        else:
            note = " (faster: consider bench_gate_baseline)" \
                if ratio < 1 / tolerance else ""
            lines.append(f"ok {name}: {cur['step_ms']:.2f} ms/step "
                         f"({ratio:.2f}x baseline){note}")
    for name in sorted(set(current) - set(baseline)):
        lines.append(f"new {name}: {current[name]['step_ms']:.2f} "
                     f"ms/step — not in baseline, not gated (add via "
                     f"bench_gate_baseline)")
    return violations, lines


def main_gate(argv):
    import argparse
    p = argparse.ArgumentParser(prog="bench.py gate")
    p.add_argument("--configs", default=None,
                   help="comma list (default: configs in the baseline, "
                        "or the chip flagship set with --update)")
    p.add_argument("--baseline", default=BASELINE_MODELS)
    p.add_argument("--tolerance", type=float, default=None,
                   help="step-time band (default: baseline file's, "
                        "else 1.25)")
    p.add_argument("--update", action="store_true",
                   help="write the measured records as the baseline")
    p.add_argument("--out", default=None,
                   help="also write this run's records to a json")
    p.add_argument("--replay", default=None,
                   help="compare a previously-written run json instead "
                        "of measuring (pure gate-logic path)")
    args = p.parse_args(argv)

    base = {}
    tol = args.tolerance
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            doc = json.load(f)
        if not args.update:
            base = doc["configs"]
        if tol is None:
            # --update inherits the file's tolerance too: an operator-
            # widened band must survive a baseline refresh
            tol = doc.get("tolerance", 1.25)
    tol = tol or 1.25

    if not base and not args.update and not args.replay:
        # fail BEFORE burning minutes of measurement that would only be
        # thrown away by the same error below
        raise SystemExit(f"no baseline at {args.baseline}; run with "
                         f"--update on a chip box first")

    flagship = ["resnet50", "resnet50_s2d", "bert_base", "llama_509m",
                "llama_509m_decode", "llama_509m_decode_int8",
                "llama_509m_serve", "llama_509m_gateway"]
    if args.replay:
        with open(args.replay) as f:
            current = json.load(f)["configs"]
    else:
        # default: every gated config PLUS the flagship set, so a new
        # config (e.g. resnet50_s2d before its first chip baseline) is
        # measured and reported even though it does not gate yet
        names = (args.configs.split(",") if args.configs
                 else sorted(set(base) | set(flagship)) if base
                 else flagship)
        current = gate_measure(names)

    meta = run_metadata()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"configs": current, "tolerance": tol,
                       "meta": meta}, f, indent=1, sort_keys=True)
    if args.update:
        with open(args.baseline, "w") as f:
            json.dump({"configs": current, "tolerance": tol,
                       "meta": meta,
                       "_provenance": "bench.py gate --update; refresh "
                       "on intentional change via ci/runtime_functions"
                       ".sh bench_gate_baseline (real-chip box)"},
                      f, indent=1, sort_keys=True)
        print(f"bench_gate: baseline written to {args.baseline} "
              f"({len(current)} configs)")
        return 0

    if not base:
        raise SystemExit(f"no baseline at {args.baseline}; run with "
                         f"--update on a chip box first")
    violations, lines = gate_compare(base, current, tol)
    if violations and not args.replay:
        # re-time violators once before failing (same policy as
        # opperf_gate)
        retimed = gate_measure([v for v in violations if v in current])
        for name, rec in retimed.items():
            if rec["step_ms"] < current[name]["step_ms"]:
                current[name] = rec
        violations, lines = gate_compare(base, current, tol)
    print("\n".join(lines))
    if violations:
        print(f"bench_gate: FAIL ({len(violations)} violation(s))")
        return 1
    print(f"bench_gate: OK ({len(base)} configs within {tol:.2f}x)")
    return 0


def _emit(rec):
    """Print ONE self-describing JSON record (meta stamped on every
    emission path, not just the aggregate mode)."""
    rec["meta"] = run_metadata()
    print(json.dumps(rec))


def main():
    from mxtpu import runtime
    # before anything compiles; installs the compile listener too (the
    # meta's compile counts)
    runtime.use_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "gate":
        raise SystemExit(main_gate(sys.argv[2:]))
    only = sys.argv[1] if len(sys.argv) > 1 else "all"
    if only not in ("all", "resnet", "bert", "llama", "smoke", "aot8b",
                    "aot8b_decode", "aot_moe", "aot8b_int8", "aot8b_32k",
                    "input", "serve", "serve_paged",
                    "gateway", "fleet", "spec", "disagg_stream"):
        raise SystemExit(
            "usage: bench.py [all|resnet|bert|llama|smoke|aot8b|"
            "aot8b_decode|aot_moe|aot8b_int8|aot8b_32k|input|serve|"
            f"serve_paged|gateway|fleet|spec|disagg_stream|"
            f"gate ...] (got {only!r})")
    if only == "serve":
        _emit(bench_llama_serve())
        return
    if only == "serve_paged":
        # the ISSUE 18 sharing workload: every request opens with the
        # same 128-token system prompt
        _emit(bench_llama_serve(shared_prefix=128))
        return
    if only == "gateway":
        _emit(bench_gateway())
        return
    if only == "fleet":
        _emit(bench_fleet())
        return
    if only == "spec":
        _emit(bench_spec())
        return
    if only == "disagg_stream":
        _emit(bench_disagg_stream())
        return
    if only == "smoke":
        _emit(bench_smoke_run())
        return
    if only == "aot8b":
        _emit(bench_aot8b())
        return
    if only == "aot8b_decode":
        _emit(bench_aot8b_decode())
        return
    if only == "aot_moe":
        _emit(bench_aot_moe())
        return
    if only == "aot8b_int8":
        _emit(bench_aot8b_int8())
        return
    if only == "aot8b_32k":
        _emit(bench_aot8b_32k())
        return
    extras = []
    img_s = mfu_r = 0.0
    stem = "std"
    if only in ("all", "resnet"):
        img_s, mfu_r, stem = bench_resnet()
        if stem != "std":
            # the headline rides the default (s2d on TPU); keep the
            # standard stem in the record so the delta is driver-visible
            img_std, mfu_std, _ = bench_resnet(stem="std")
            extras.append({"metric": "resnet50_std_stem_img_s",
                           "value": round(img_std, 1), "unit": "img/s",
                           "mfu": round(mfu_std, 3), "stem": "std",
                           "vs_baseline": round(
                               img_std / BASELINE_RESNET_IMG_S, 3)})
    if only in ("all", "bert"):
        s_s, mfu_b = bench_bert()
        extras.append({"metric": "bert_base_pretrain_samples_per_s",
                       "value": round(s_s, 1), "unit": "samples/s",
                       "mfu": round(mfu_b, 3),
                       "vs_baseline": round(s_s / BASELINE_BERT_SAMPLES_S,
                                            3)})
    if only == "input":
        _emit(bench_input_pipeline())
        return
    if only in ("all", "llama"):
        t_s, mfu_l, n_p = bench_llama()
        extras.append({"metric": "llama_500m_train_tokens_per_s",
                       "value": round(t_s, 1), "unit": "tok/s",
                       "mfu": round(mfu_l, 3), "n_params": n_p,
                       "vs_baseline": None})
        d_s = bench_llama_decode()
        extras.append({"metric": "llama_500m_decode_tokens_per_s",
                       "value": round(d_s, 1), "unit": "tok/s",
                       "vs_baseline": None})
        q_s = bench_llama_decode(int8=True)
        extras.append({"metric": "llama_500m_decode_int8_tokens_per_s",
                       "value": round(q_s, 1), "unit": "tok/s",
                       "vs_baseline": None})
        extras.append(bench_llama_serve())
        extras.append(bench_gateway())
    if only == "all":
        extras.append(bench_input_pipeline())
        extras.append(bench_spec())
        extras.append(bench_disagg_stream())
        extras.append(bench_fleet())
    out = {
        "metric": "resnet50_train_throughput_per_chip",
        "value": round(img_s, 1),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_RESNET_IMG_S, 3),
        "mfu": round(mfu_r, 3),
        "stem": stem,
        "extra": extras,
    }
    if only != "all" and extras:
        # sub-benchmark: promote its FIRST record (the headline —
        # llama's train tok/s, not the decode extra) and nest the rest
        # ('extra' always present: every mode emits a uniform shape)
        out = dict(extras[0], extra=extras[1:])
    _emit(out)


if __name__ == "__main__":
    main()
