"""chip_smoke.py — the quickest proof that mxtpu still starts on the chip.

One process drives both halves of the hot path once, through the entry
points a user and ``bench.py`` call, at the widths ``bench.py`` runs the
repo's llama at (``GATE_CONFIGS["llama_509m*"]``): the mesh trainer for
a few steps, then a paged ``ServeEngine`` behind the HTTP gateway for a
dozen requests. The weights are random, made from a seed. Each phase
prints one ``PASS``/``FAIL`` line with its set-up (compile) seconds
apart from its run seconds; those seconds are set-up facts, not
metrics. Any ``FAIL`` or exception ends the run non-zero, and so does
a JAX that finds no TPU: there is no flag that lets it pass without
one. The last line of standard output is the result,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run it alone: a chip belongs to one process at a time.

    python chip_smoke.py
"""
from __future__ import annotations

import contextlib
import faulthandler
import json
import os
import re
import sys
import threading
import time
import traceback
from dataclasses import replace

# bench.py's llama widths (bench_llama, bench_llama_serve): 509M
# parameters, sized for one v5e chip
WIDTHS = dict(vocab_size=32000, dim=2048, n_layers=8, n_heads=16,
              n_kv_heads=8, hidden_dim=5632)
# (prompt length, new tokens, temperature): four program shapes for the
# per-request reference, three requests of each. The prompt lengths are
# no multiple of the page size, so a prompt's last page is partial and
# registering it runs the copy_page program.
SERVE_SHAPES = ((50, 24, 0.0), (100, 40, 0.7), (200, 16, 0.0),
                (200, 64, 0.7))
SERVE_ENGINE = dict(max_slots=8, max_len=768, min_bucket=64)
# the same for the sambay and latent_moe legs, all greedy: the longer
# prompt wraps a 512-key window ring three times, and is two chunks
SAMBAY_SHAPES = ((300, 16, 0.0), (1700, 24, 0.0))


def _compiles() -> int:
    """Programs jax has built or fetched from its cache in this
    process, as the repo's compile listener counts them."""
    from mxtpu import telemetry
    return int(telemetry.registry().value("jax_compile_total"))


# -- device -----------------------------------------------------------------
def phase_device(cache_dir):
    """jax must see TPUs of a kind the peaks table holds, and the
    compile listener must be counting. Nothing here has a default."""
    import importlib.metadata as md
    import jax
    import jaxlib
    from mxtpu import telemetry
    from mxtpu.telemetry import perfscope

    t0 = time.perf_counter()
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices() reports {len(devs)} "
            f"{d0.platform!r} device(s) (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); "
            "chip_smoke.py only passes on a chip")
    spec = perfscope.spec_for(d0.device_kind)     # unknown kind raises
    if spec.kind == "cpu":
        raise RuntimeError(f"peaks table maps {d0.device_kind!r} to "
                           "its CPU row")
    if not (telemetry.enabled()
            and telemetry.install_compile_listener()):
        raise RuntimeError("the compile listener is not installed; "
                           "'no compile in the window' would be "
                           "trivially true")
    return {"setup_s": time.perf_counter() - t0, "run_s": 0.0,
            "platform": d0.platform, "device_kind": d0.device_kind,
            "count": len(devs), "peaks": spec.kind,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": md.version("libtpu"), "compile_cache": cache_dir}


# -- context ----------------------------------------------------------------
def phase_context(ctx, n=8192, chain=24):
    """The README's typical use against a real ``mx.tpu()``, and the
    fence check: a chain of large matmuls timed to
    ``block_until_ready`` (``NDArray.wait_to_read``), to
    ``mx.nd.waitall`` and to a scalar read-back must agree, and none
    may beat the chip's peak."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxtpu as mx
    from mxtpu import autograd, gluon
    from mxtpu.gluon import nn
    from mxtpu.telemetry import perfscope

    t0 = time.perf_counter()
    dev = ctx.jax_device()
    rng = np.random.default_rng(0)
    x_np = rng.standard_normal((64, 32)).astype(np.float32)
    y_np = rng.standard_normal((64, 4)).astype(np.float32)
    x = mx.nd.array(x_np, ctx=ctx)
    assert x.context == ctx and x._data.devices() == {dev}, x.context
    hop = x.as_in_context(mx.cpu()).as_in_context(ctx) \
        .as_in_context(mx.cpu())
    assert hop.context == mx.cpu()
    np.testing.assert_array_equal(hop.asnumpy(), x_np)
    y = mx.nd.array(y_np, ctx=ctx)

    def train(hybridize):
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(64, activation="tanh", in_units=32),
                    nn.Dense(4, in_units=64))
        mx.random.seed(7)
        net.initialize(ctx=ctx)
        if hybridize:
            net.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        losses = []
        for _ in range(4):
            with autograd.record():
                loss = ((net(x) - y) ** 2).mean()
            loss.backward()
            tr.step(1)
            losses.append(float(loss.asscalar()))
        assert net[0].weight.data().context == ctx
        return losses

    eager, hybrid = train(False), train(True)
    assert all(np.isfinite(eager)) and eager[-1] < eager[0], eager
    # one XLA program against op-by-op dispatch: the same numbers up to
    # the backend's default matmul precision
    np.testing.assert_allclose(hybrid, eager, rtol=2e-2)
    setup_s = time.perf_counter() - t0

    # the fence check
    t0 = time.perf_counter()
    a = jax.device_put(
        (rng.standard_normal((n, n)) / np.sqrt(n)).astype(np.float32),
        dev).astype(jnp.bfloat16)
    step = jax.jit(lambda y, a: y @ a)
    first = jax.jit(lambda y: y[0, 0].astype(jnp.float32))
    float(first(step(a, a)))                      # compile both

    def timed(fence):
        y = a
        t = time.perf_counter()
        for _ in range(chain):
            y = step(y, a)
        fence(y)
        return time.perf_counter() - t

    def waitall(y):
        mx.nd.waitall()
        assert y.is_ready(), "waitall returned before the chain ended"

    t_read = min(timed(lambda y: float(first(y))) for _ in range(2))
    t_ready = min(timed(lambda y: mx.nd.from_jax(y).wait_to_read())
                  for _ in range(2))
    t_all = min(timed(waitall) for _ in range(2))
    floor = chain * 2 * n ** 3 / perfscope.spec_for(
        dev.device_kind).peak_flops
    for t in (t_read, t_ready, t_all):
        assert t >= floor and abs(t - t_read) <= 0.25 * t_read, \
            (t_read, t_ready, t_all, floor)
    return {"setup_s": setup_s, "run_s": time.perf_counter() - t0,
            "eager_vs_hybrid_max_rel": float(np.max(np.abs(
                np.array(hybrid) / np.array(eager) - 1))),
            "scalar_readback_ms": round(1e3 * t_read, 1),
            "block_until_ready_ms": round(1e3 * t_ready, 1),
            "waitall_ms": round(1e3 * t_all, 1),
            "peak_floor_ms": round(1e3 * floor, 1)}


# -- train ------------------------------------------------------------------
def _shard_report(tree, mesh):
    """Bytes of ``tree`` each device of ``mesh`` holds, and the tree's
    global bytes — state that is spread holds a fraction everywhere."""
    import jax
    per_dev = {d.id: 0 for d in mesh.devices.flat}
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    return per_dev, total


def phase_train(cfg, batch, seq, steps, mesh_axes=None,
                expect_attn="pallas"):
    """What ``bench_llama`` builds — mesh, ``init_state``,
    ``make_train_step`` over ``llama.loss_fn`` with adamw — stepped on
    one repeated batch. ``expect_attn`` names the attention the
    compiled step must hold: ``pallas`` (the Mosaic custom call),
    ``blockwise`` (the scan, off-TPU) or ``ring`` (collective
    permutes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep
    from mxtpu import telemetry
    from mxtpu.telemetry import perfscope

    t0 = time.perf_counter()
    mesh = pmesh.create_mesh(**(mesh_axes or {"dp": -1}))
    rules = llama.sharding_rules(cfg)
    tx = optax.adamw(3e-4)
    state = pstep.init_state(
        llama.init_params(cfg, jax.random.PRNGKey(0)), tx, mesh, rules)
    train_step = pstep.make_train_step(
        llama.loss_fn(cfg, mesh=mesh), tx, mesh, rules)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)
    batch_d = {"tokens": tokens}
    c0 = _compiles()
    state, loss = train_step(state, batch_d)
    losses = [float(jax.device_get(loss))]
    c1 = _compiles()
    assert c1 > c0, "the compile listener did not see the step compile"
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps - 1):
        state, loss = train_step(state, batch_d)
        losses.append(float(jax.device_get(loss)))
    run_s = time.perf_counter() - t0
    assert _compiles() == c1, \
        f"{_compiles() - c1} compile(s) after the first step"
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    # which attention the program holds, read off the program itself
    text = train_step._jitted.lower(state, batch_d, None) \
        .as_text(debug_info=True)
    kernels = re.findall(
        r"custom_call @tpu_custom_call\(.*?: \((tensor<[^>]*>)", text)
    held = ("pallas" if kernels
            else "ring" if "collective_permute" in text
            else "blockwise" if "flash_attention_blockwise" in text
            else "other")
    assert held == expect_attn, (
        f"the compiled step holds {held!r} attention, not "
        f"{expect_attn!r}")
    if held == "pallas":
        assert "flash_attention_pallas" in text

    # the watcher cataloged the step when it compiled, from the
    # executable that call built: cataloging again builds nothing
    cost = perfscope.catalog().get("train_step")
    assert cost is not None and cost.flops > 0 and cost.peak_hbm_bytes, \
        f"perfscope holds no costs for the train step: {cost}"
    again = perfscope.profile_program(
        train_step._jitted, "train_step", (state, batch_d, None))
    assert again is not None and again.flops == cost.flops, again
    assert _compiles() == c1, "cataloging the step compiled a program"
    prog = telemetry.programs()["train_step"]

    info = {"setup_s": setup_s, "run_s": run_s,
            "mesh": {a: n for a, n in mesh.shape.items() if n > 1}
            or {"dp": 1},
            "loss": [round(l, 3) for l in (losses[0], losses[-1])],
            "steps": steps, "attention": held,
            # what the checkpointed layers keep (None: the policy is
            # the config's own and no plan was made) and the compiled
            # step's memory_analysis(), a device
            "remat_plan": prog.remat_plan and list(prog.remat_plan),
            "remat_saved_gb": round(prog.remat_saved_bytes / 1e9, 3),
            "catalog_per_device": {
                "gflop": round(cost.flops / 1e9, 1),
                "gb_accessed": round(cost.bytes_accessed / 1e9, 2),
                "argument_gb": round((cost.argument_bytes or 0) / 1e9, 2),
                "temp_gb": round((cost.temp_bytes or 0) / 1e9, 2),
                "peak_hbm_gb": round(cost.peak_hbm_bytes / 1e9, 2)}}
    if kernels:
        info["mosaic_calls"] = len(kernels)
        info["kernel_operand"] = kernels[0]
    if mesh.size > 1:
        per_dev, total = _shard_report(state.params, mesh)
        info["param_bytes_per_device"] = per_dev
        info["param_bytes"] = total
        if mesh_axes:           # a model-parallel layout: truly spread
            assert all(0 < b <= 0.6 * total
                       for b in per_dev.values()), (per_dev, total)
    return info


# -- serve ------------------------------------------------------------------
def make_jobs(vocab, shapes, per_shape=3, shared_prefix=128, seed=0):
    """The request list: ``per_shape`` requests of each (prompt length,
    new tokens, temperature), with random prompts from ``seed``. The
    second request of the last greedy shape repeats the first one's
    leading ``shared_prefix`` tokens and waits for it to finish, so its
    admission must hit the prefix cache."""
    import numpy as np
    rng = np.random.default_rng(seed)
    jobs = []
    for plen, mnew, temp in shapes:
        for _ in range(per_shape):
            jobs.append({"prompt": rng.integers(0, vocab, plen).tolist(),
                         "mnew": mnew, "temperature": temp,
                         "seed": len(jobs), "after": None, "shared": 0})
    greedy = [i for i, j in enumerate(jobs) if j["temperature"] == 0.0
              and len(j["prompt"]) > shared_prefix]
    first, second = greedy[-per_shape], greedy[-per_shape + 1]
    jobs[second]["prompt"][:shared_prefix] = \
        jobs[first]["prompt"][:shared_prefix]
    jobs[second].update(after=first, shared=shared_prefix)
    return jobs


def _references(cfg, params, jobs):
    """Each job's stream from per-request ``llama.generate`` — the
    contract every serve test asserts against."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.models import llama
    fns, out = {}, []
    for job in jobs:
        key = (job["mnew"], job["temperature"])
        if key not in fns:
            fns[key] = jax.jit(lambda p, t, r, k=key: llama.generate(
                cfg, p, t, k[0], temperature=k[1], rng=r))
        toks = fns[key](params, jnp.asarray(job["prompt"], jnp.int32)[None],
                        jax.random.PRNGKey(job["seed"]))
        out.append(np.asarray(toks)[0, len(job["prompt"]):].tolist())
    return out


@contextlib.contextmanager
def _matmul_precision(precision):
    """jax's default matmul precision, process-wide: the config's own
    context manager is per thread, and the engine traces on its own."""
    import jax
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", precision)
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", prev)


def _serve_jobs(cfg, params, jobs, mesh, engine_kw, t0):
    """The serving half of :func:`phase_serve`: gateway up, warm-up,
    the jobs, the checks, gateway down. Set-up counts from ``t0``.
    Returns (info, streams)."""
    import numpy as np
    from mxtpu.serve import ServeEngine
    from mxtpu.serve.engine import bucket_for
    from mxtpu.serve.gateway import Gateway, GatewayClient
    from mxtpu.telemetry import perfscope

    seen = {p: c.variants for p, c in perfscope.catalog().items()}
    # a cold compile may outlast the supervisor's default stall
    # threshold; a replica restarted mid-compile never finishes one
    gw = Gateway(lambda: ServeEngine(
        cfg, params, prefix_cache=True, mesh=mesh, **engine_kw),
        n_replicas=1, queue_max=4 * len(jobs),
        supervisor_opts={"stall_s": 900.0, "warmup_s": 900.0})
    try:
        port = gw.start_http(port=0)
        engine = gw.backend.replicas()[0].engine
        min_bucket, max_len = engine.min_bucket, engine.max_len

        def ask(job):
            return GatewayClient("127.0.0.1", port, timeout=900).generate(
                job["prompt"], job["mnew"], seed=job["seed"],
                temperature=job["temperature"])

        # warm-up: every prefill bucket the jobs use (a prefix hit
        # prefills only the suffix), the decode program and copy_page,
        # one request at a time
        buckets = sorted({bucket_for(len(j["prompt"]) - j["shared"],
                                     min_bucket, max_len) for j in jobs})
        wrng = np.random.default_rng(1)
        warm_s = {}
        for b in buckets:
            tw = time.perf_counter()
            rec = ask({"prompt": wrng.integers(
                           0, cfg.vocab_size,
                           min(b, max_len - 1) - 1).tolist(),
                       "mnew": 2, "temperature": 0.7, "seed": 10 ** 6 + b})
            assert rec["status"] == 200 and len(rec["tokens"]) == 2, rec
            warm_s[b] = round(time.perf_counter() - tw, 1)
        bound = engine.n_buckets + 2        # + decode + copy_page
        compiled = engine.compile_count
        assert engine.n_buckets == len(buckets), (engine.n_buckets, buckets)
        assert compiled <= bound, (compiled, bound)
        # each of them cataloged from its executable as it compiled
        programs = ["serve_decode", "serve_copy_page"] + [
            f"serve_prefill_b{b}" for b in buckets]
        now = perfscope.catalog()
        uncataloged = [p for p in programs if p not in now
                       or now[p].variants <= seen.get(p, 0)
                       or not now[p].peak_hbm_bytes]
        assert not uncataloged, f"perfscope holds no costs for {uncataloged}"
        c_warm = _compiles()
        setup_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        results = [None] * len(jobs)
        done = [threading.Event() for _ in jobs]

        def fire(i):
            try:
                if jobs[i]["after"] is not None:
                    done[jobs[i]["after"]].wait(900)
                results[i] = ask(jobs[i])
            finally:
                done[i].set()

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        assert not any(t.is_alive() for t in threads), "a client hung"
        run_s = time.perf_counter() - t0
        for job, rec in zip(jobs, results):
            assert rec is not None and rec["status"] == 200, rec
            assert len(rec["tokens"]) == job["mnew"] and \
                rec["reason"] == "complete", (job["mnew"], rec)
        assert engine.compile_count == compiled, \
            (engine.compile_count, compiled)
        assert _compiles() == c_warm, \
            f"{_compiles() - c_warm} compile(s) after warm-up"
        kv = engine.kv_cache_stats()
        assert kv["prefix_hits"] >= 1, kv
        client = GatewayClient("127.0.0.1", port)
        status, health = client.get_json("/healthz")
        assert status == 200 and health["status"] == "ok", health
        status, metrics = client.get_text("/metrics")
        assert status == 200 and "serve_" in metrics, metrics[:200]

        info = {"setup_s": setup_s, "run_s": run_s,
                "decode_attention": kv["decode_attention"],
                "sampler": kv["sampler"],
                "requests": len(jobs),
                "tokens": sum(j["mnew"] for j in jobs),
                "buckets": buckets, "warmup_s": warm_s,
                "compiles": compiled, "compile_bound": bound,
                "prefix_hits": kv["prefix_hits"],
                "cow_forks": kv["cow_forks"]}
        if mesh is not None:
            per_dev, total = _shard_report(engine._kv, mesh)
            assert all(0 < b < total for b in per_dev.values()), per_dev
            info.update(mesh={a: n for a, n in mesh.shape.items()
                              if n > 1},
                        kv_spec=str(engine._kv["k"].sharding.spec),
                        kv_bytes_per_device=per_dev, kv_bytes=total)
    finally:
        gw.close()
    return info, [r["tokens"] for r in results]


def phase_serve(cfg, jobs, *, mesh=None, precision=None, compare_to=None,
                must_match=False, expect_attention=None,
                expect_sampler=None, **engine_kw):
    """A paged ``ServeEngine`` (prefix cache on) behind
    ``Gateway.start_http``, asked ``jobs`` by ``GatewayClient`` threads
    of this process. Every request must come back 200 and whole, the
    engine must stay inside its own compile bound with nothing compiled
    after warm-up, and ``/healthz`` and ``/metrics`` must answer. Then
    the streams are compared, token for token, with ``compare_to`` or —
    when that is None — with per-request ``llama.generate``; how many
    agree is returned, and asserted only under ``must_match`` (what
    holds on the chip: float32 at highest precision; bf16 streams part
    from ``generate`` at near-ties). ``precision`` is jax's default
    matmul precision for the phase; ``engine_kw`` shapes the engine.
    ``expect_attention`` is what the engine must say its decode program's
    attention was built on (``"pages"``: the Pallas kernel over live
    pages, a bf16 pool on one chip; ``"gathered"``: everything else),
    ``expect_sampler`` the same for its sampler's threshold search
    (``"search_kernel"``: a bank of eight slots or more on one chip)."""
    import jax
    import numpy as np
    from mxtpu.models import llama
    from mxtpu.parallel.sharding import shard_pytree

    against = ("llama.generate" if compare_to is None
               else "the one-device engine")
    t0 = time.perf_counter()
    with _matmul_precision(precision):
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        if mesh is not None:
            params = shard_pytree(params, mesh, llama.sharding_rules(cfg))
        info, streams = _serve_jobs(cfg, params, jobs, mesh, engine_kw, t0)
        if compare_to is None:
            compare_to = _references(cfg, params, jobs)
    parted = {}
    for i, (got, want) in enumerate(zip(streams, compare_to)):
        if got != want:
            parted[i] = next(k for k, (a, b) in enumerate(zip(got, want))
                             if a != b)
    info.update(dtype=np.dtype(cfg.dtype).name,
                precision=precision or "default", compared_with=against,
                identical=f"{len(jobs) - len(parted)}/{len(jobs)}",
                first_difference_at=parted)
    assert not (must_match and parted), \
        f"streams parted from {against} at {parted}"
    assert expect_attention in (None, info["decode_attention"]), info
    assert expect_sampler in (None, info["sampler"]), info
    return info, streams


def phase_pages_kernel(*, slots=32, n_heads=32, n_kv_heads=8, head_dim=128,
                       page_size=16, capacity=2048, layers=2,
                       interpret=False):
    """The pages kernel (``ops/paged_attention.py``) against the
    gathered path on ONE random bf16 pool at Mistral-7B's head shapes:
    ``slots`` slots of ragged lengths 1..``capacity`` (1, a page's edge
    and ``capacity`` among them) over shuffled pages, the last layer of
    ``layers`` by a traced index. Prints the largest difference; it may
    be a few bf16 roundings of the largest output (the two sum in another
    order). ``interpret`` runs the kernel interpreted (the CPU test)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.ops.attention import gathered_decode_attention
    from mxtpu.ops.paged_attention import paged_attention_pages

    t0 = time.perf_counter()
    per_slot = capacity // page_size
    n_pages = 1 + slots * per_slot
    rng = np.random.default_rng(0)
    lengths = rng.integers(1, capacity + 1, slots).astype(np.int32)
    lengths[:4] = 1, capacity, page_size, page_size + 1
    table = (1 + rng.permutation(n_pages - 1)).astype(np.int32).reshape(
        slots, per_slot)
    shape = (layers, n_pages, page_size, n_kv_heads, head_dim)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16))
    kp, vp = pool(keys[0]), pool(keys[1])
    q = jax.random.normal(keys[2], (slots, n_heads, 1, head_dim),
                          jnp.bfloat16)
    layer = jnp.int32(layers - 1)
    kernel = jax.jit(lambda *a: paged_attention_pages(
        *a, layer=layer, interpret=interpret))
    gathered = jax.jit(lambda *a: gathered_decode_attention(*a, layer=layer))
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(lengths))
    got = np.asarray(kernel(*args), np.float32)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = np.asarray(gathered(*args), np.float32)
    worst, largest = float(np.abs(got - want).max()), float(
        np.abs(want).max())
    assert np.isfinite(got).all()
    assert worst <= 4 * 2.0 ** -8 * max(1.0, largest), (worst, largest)
    return {"setup_s": setup_s, "run_s": time.perf_counter() - t0,
            "slots": slots, "lengths": [int(lengths.min()),
                                        int(lengths.max())],
            "pool_bytes": 2 * int(np.prod(shape)) * 2,
            "largest_difference": worst, "largest_output": largest}


def phase_latent_kernel(*, slots=32, n_heads=32, row=640, value_dim=512,
                        page_size=16, capacity=4096, layers=2,
                        interpret=False):
    """:func:`phase_pages_kernel` for a latent pool
    (``ops.paged_attention.paged_latent_pages`` against
    ``ops.attention.gathered_latent_decode_attention``) at
    ``models/latent_moe.py``'s published shapes: 32 heads over ONE row
    of 640 a token, values its first 512."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.ops.attention import gathered_latent_decode_attention
    from mxtpu.ops.paged_attention import paged_latent_pages

    t0 = time.perf_counter()
    per_slot = capacity // page_size
    n_pages = 1 + slots * per_slot
    rng = np.random.default_rng(0)
    lengths = rng.integers(1, capacity + 1, slots).astype(np.int32)
    lengths[:4] = 1, capacity, page_size, page_size + 1
    table = (1 + rng.permutation(n_pages - 1)).astype(np.int32).reshape(
        slots, per_slot)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.jit(lambda k: jax.random.normal(
        k, (layers, n_pages, page_size, row), jnp.bfloat16))(keys[0])
    q = jax.random.normal(keys[1], (slots, n_heads, 1, row), jnp.bfloat16)
    layer, scale = jnp.int32(layers - 1), 1.0 / 24
    kernel = jax.jit(lambda *a: paged_latent_pages(
        *a, layer=layer, scale=scale, interpret=interpret))
    gathered = jax.jit(lambda *a: gathered_latent_decode_attention(
        *a, layer=layer, value_dim=value_dim, scale=scale))
    args = (q, pool, jnp.asarray(table), jnp.asarray(lengths))
    want = np.asarray(gathered(*args), np.float32)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = np.asarray(kernel(*args), np.float32)[..., :value_dim]
    worst, largest = float(np.abs(got - want).max()), float(
        np.abs(want).max())
    assert np.isfinite(got).all()
    assert worst <= 4 * 2.0 ** -8 * max(1.0, largest), (worst, largest)
    return {"setup_s": setup_s, "run_s": time.perf_counter() - t0,
            "slots": slots, "lengths": [int(lengths.min()),
                                        int(lengths.max())],
            "pool_bytes": int(np.prod(pool.shape)) * 2,
            "largest_difference": worst, "largest_output": largest}


def phase_retention_kernel(*, slots=16, n_heads=40, kv_heads=8, head_dim=128,
                           layers=2, steps=5, interpret=False):
    """The Pallas decode step of a power-retention layer
    (``ops.retention.retention_step_bank``: a layer's state read once
    and written once, in place, the read-out accumulated tile by tile)
    against the ``jnp`` form (``retention_step`` on the layer's slice) on
    one random float32 bank at ``models/retention.py``'s published
    shapes: the state written equal to float32's rounding, the outputs
    to bf16's (the kernel's read-out multiplies bf16 operands). Then
    ``steps`` timed steps of the kernel, a layer each: ``kernel_ms`` and
    the share of the memory's speed (2 x a layer's state a step)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.ops import retention as ops

    t0 = time.perf_counter()
    F = ops.sympow2_rows(head_dim)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    S = jax.jit(lambda k: jax.random.normal(
        k, (layers, slots, kv_heads, head_dim, F), jnp.float32))(ks[0])
    z = 30.0 + jax.random.normal(ks[1], (layers, slots, kv_heads, F))
    q = jax.random.normal(ks[2], (slots, n_heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(ks[3], (slots, kv_heads, head_dim), jnp.bfloat16)
    v = jax.random.normal(ks[4], (slots, kv_heads, head_dim), jnp.bfloat16)
    log_g = jax.nn.log_sigmoid(4.0 + jax.random.normal(
        ks[5], (slots, kv_heads)))
    layer, kw = jnp.int32(layers - 1), dict(scale=head_dim ** -0.5)
    want_y, want_S, want_z = jax.jit(lambda q, k, v, g, S, z: (
        ops.retention_step(q, k, v, g, S[layers - 1], z[layers - 1], **kw)
    ))(q, k, v, log_g, S, z)
    want_y, want_S, want_z = (np.asarray(a) for a in
                              (want_y, want_S, want_z))
    untouched = np.asarray(S[0, 0, 0])
    kernel = jax.jit(lambda q, k, v, g, S, z: ops.retention_step_bank(
        q, k, v, g, S, z, layer, interpret=interpret, **kw),
        donate_argnums=(4, 5))
    if not interpret:
        assert ops.retention_step_path(S.shape, S.dtype) == "kernel"
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    y, S, z = kernel(q, k, v, log_g, S, z)
    y = np.asarray(y)
    assert np.isfinite(y).all()
    worst_S = float(np.abs(np.asarray(S[layers - 1]) - want_S).max())
    worst_y, largest = float(np.abs(y - want_y).max()), float(
        np.abs(want_y).max())
    assert worst_S <= 1e-5 * float(np.abs(want_S).max()), worst_S
    np.testing.assert_allclose(np.asarray(z[layers - 1]), want_z, rtol=1e-6)
    assert worst_y <= 8 * 2.0 ** -8 * max(1.0, largest), (worst_y, largest)
    np.testing.assert_array_equal(np.asarray(S[0, 0, 0]), untouched)
    jax.block_until_ready(S)
    t1 = time.perf_counter()
    for _ in range(steps):
        y, S, z = kernel(q, k, v, log_g, S, z)
    jax.block_until_ready((y, S))
    kernel_ms = 1e3 * (time.perf_counter() - t1) / steps
    moved = 2 * slots * kv_heads * head_dim * F * 4
    return {"setup_s": setup_s, "run_s": time.perf_counter() - t0,
            "slots": slots, "state_bytes_a_layer": moved // 2,
            "largest_difference": worst_y, "largest_output": largest,
            "largest_state_difference": worst_S, "kernel_ms": kernel_ms,
            "hbm_share": moved / (kernel_ms * 1e-3) / 819e9}


# (rows, vocabulary, temperature): the four serve cells' decode samples,
# and the one row a last prefill chunk samples in the agent cell (fewer
# than eight rows: the ``jnp`` search ships there, not the kernel)
SAMPLER_SHAPES = ((32, 200064, 0.6), (32, 128256, 0.7), (16, 151936, 0.7),
                  (32, 32768, 0.7), (1, 200064, 0.6))


def _sorted_thresholds(lg, k, p):
    """The sampler's two thresholds as it found them from PR 28 to PR 33,
    frozen here as the comparison: ONE sort of the values, ``kth`` from
    the sorted row, the nucleus by ``cumsum`` over the softmax of the
    thresholded sorted row."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    srt = -lax.sort(-lg, dimension=-1, is_stable=False)
    kth = jnp.take_along_axis(srt, k - 1, axis=-1)
    srt = jnp.where(srt < kth, -jnp.inf, srt)
    probs = jax.nn.softmax(srt, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < p
    return kth, jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                        keepdims=True)


def _nucleus_slack(row, kth, cut, p):
    """How far a row's cut-off lies from the nucleus's boundary, as
    float64 ``numpy`` reckons the mass: 0.0 where ``cut`` is the
    smallest value with less than ``p`` of the survivors' mass above it,
    else the mass by which it is too high or too low."""
    import numpy as np
    x = np.sort(row[row >= kth].astype(np.float64))[::-1]
    mass = np.exp(x - x[0])
    csum = np.cumsum(mass / mass.sum())
    above = csum[np.searchsorted(-x, -float(cut), side="left") - 1] \
        if cut < x[0] else 0.0
    through = csum[np.searchsorted(-x, -float(cut), side="right") - 1]
    return max(above - p, p - through, 0.0)


def phase_sampler_search(*, shapes=SAMPLER_SHAPES, top_p=0.95, top_k=40,
                         calls=20, interpret=False):
    """The sampler's threshold search (``ops.threshold.thresholds``: on
    a TPU the Pallas kernel, eight rows resident in VMEM, for a block
    of eight rows or more) against the frozen sort form on random rows
    at the four serve cells' rows x vocabulary and one row of the
    largest, twice a shape: ``top_p`` alone (the cells' requests),
    then with ``top_k`` on every other row. ``kth`` must equal the
    sort's bit for bit; the cut-off must BE the nucleus's boundary to
    within 2e-6 of mass as float64 reckons it (the float32 sums are
    taken in another order than ``cumsum``'s; how many rows equal the
    sort's cut-off bit for bit is printed, and the sort's own slack).
    Then ms a call of the sort, the ``jnp`` search and the search that
    ships, for ``top_p`` alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.ops import threshold

    t0 = time.perf_counter()
    run = jax.jit(lambda lg, k, p: threshold.thresholds(
        lg, k, p, interpret=interpret))
    forms = {"sort": jax.jit(_sorted_thresholds),
             "search_jnp": jax.jit(threshold._thresholds_jnp),
             "ships": run}
    setup_s, t0 = time.perf_counter() - t0, time.perf_counter()
    facts = {"path": "interpreted" if interpret else {
        f"{rows}x{V}": threshold.thresholds_path((rows, V), jnp.float32)
        for rows, V, _ in shapes}}
    equal = rows_seen = 0
    worst = worst_sort = 0.0
    for rows, V, temperature in shapes:
        lg = jax.random.normal(jax.random.PRNGKey(V), (rows, V),
                               jnp.float32) / temperature
        p = jnp.full((rows, 1), top_p, jnp.float32)
        off = jnp.full((rows, 1), V, jnp.int32)
        some = jnp.where(jnp.arange(rows)[:, None] % 2 == 0, top_k, off)
        host = np.asarray(lg)
        for k in (off, some):
            (kth, cut), (want_kth, want_cut) = (
                [np.asarray(a)[:, 0] for a in f(lg, k, p)]
                for f in (run, forms["sort"]))
            asked = np.asarray(k)[:, 0] < V
            np.testing.assert_array_equal(kth[asked], want_kth[asked])
            assert (kth[~asked] == -np.inf).all()
            for i in range(rows):
                worst = max(worst, _nucleus_slack(
                    host[i], kth[i], cut[i], top_p))
                worst_sort = max(worst_sort, _nucleus_slack(
                    host[i], kth[i], want_cut[i], top_p))
            equal += int((cut == want_cut).sum())
            rows_seen += rows
        ms = {}
        for name, f in forms.items():
            jax.block_until_ready(f(lg, off, p))
            t1 = time.perf_counter()
            for _ in range(calls):
                out = f(lg, off, p)
            jax.block_until_ready(out)
            ms[name] = round(1e3 * (time.perf_counter() - t1) / calls, 4)
        facts[f"ms_{rows}x{V}"] = ms
    assert worst <= 2e-6, worst
    facts.update(cutoffs_equal_to_sort=f"{equal}/{rows_seen}",
                 worst_slack=worst, worst_slack_of_sort=worst_sort)
    return {"setup_s": setup_s, "run_s": time.perf_counter() - t0, **facts}


# the chat cell's engine (``benchmark/grid/configs``' Mistral serve file)
# at two layers: the layers are a loop, so depth changes no program
WARM_SETUP_WIDTHS = dict(vocab_size=32768, dim=4096, n_layers=2, n_heads=32,
                         n_kv_heads=8, hidden_dim=14336)
WARM_SETUP_ENGINE = dict(max_slots=32, max_len=2048, min_bucket=128,
                         page_size=16, n_pages=2049, prefix_cache=True)
_BUILD_FIELDS = ("trace_s", "nested_traces", "lower_s", "backend_s",
                 "cache", "first_call_s")


def phase_serve_warm_setup(cfg, *, buckets=(128, 256, 512, 1024),
                           **engine_kw):
    """Where a serve cell's set-up seconds go, program by program,
    without the benchmark: the chat-shaped engine built TWICE in this
    process against the one compile cache directory, jax's in-memory
    caches dropped in between, so that the second pass is what a warm
    run pays (trace, lower, fetch). A pass asks one request a prefill
    bucket, two tokens each (the first also runs the decode program and
    ``copy_page``), and clocks each request; beside it the program's own
    record of each build (``telemetry.programs()``: the seconds of the
    trace, the traces nested in it, the lowering and the backend, the
    persistent cache's answer, the building call's wall time), and the
    seconds of what was built outside the engine's programs (casts,
    seeds: ``others``). What the first pass compiled and wrote
    (``miss``), the second must find (``hit``): a program that misses
    there is compiled in every run of every cell that holds it
    (PERF.md, PR 34-35). jax writes no executable that compiled in
    under ``jax_persistent_cache_min_compile_time_secs``: those read
    ``unwritten`` (every run compiles them again) and are named, not
    failed."""
    import jax
    import numpy as np
    from mxtpu import telemetry
    from mxtpu.models import llama
    from mxtpu.serve import Request, ServeEngine

    t0 = time.perf_counter()
    params = jax.jit(lambda k: llama.init_params(cfg, k))(
        jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    setup_s, t0 = time.perf_counter() - t0, time.perf_counter()
    rng = np.random.default_rng(2)

    def rounded(v):
        return round(v, 2) if isinstance(v, float) else v

    def others_s():
        o = telemetry.programs().get("others")
        return (o.trace_s, o.lower_s, o.backend_s) if o else (0.0,) * 3

    def one_pass():
        before, catalogued = others_s(), telemetry.programs()
        engine = ServeEngine(cfg, params, paged=True, **engine_kw)
        calls = {}
        for b in buckets:
            t1 = time.perf_counter()
            engine.submit(Request(
                prompt=rng.integers(0, cfg.vocab_size, b - 1).tolist(),
                max_new_tokens=2, temperature=0.7, top_p=0.95, seed=7))
            engine.run()
            calls[f"b{b}"] = round(time.perf_counter() - t1, 2)
        assert engine.n_buckets == len(buckets), engine.n_buckets
        # what this pass built: a build makes the catalog a new entry
        rows = {n: {k: rounded(getattr(p, k)) for k in _BUILD_FIELDS}
                for n, p in sorted(telemetry.programs().items())
                if n.startswith("serve_") and p is not catalogued.get(n)}
        rows["others"] = dict(zip(
            ("trace_s", "lower_s", "backend_s"),
            (round(a - b, 2) for a, b in zip(others_s(), before))))
        return calls, rows, engine.kv_cache_stats()

    # both builds from ONE line: a kernel's payload holds its call
    # sites, and another line here would be another cache key
    passes = []
    for _ in range(2):
        jax.clear_caches()
        passes.append(one_pass())
    (first_calls, first, kv), (second_calls, second, _) = passes
    unwritten = sorted(n for n, r in first.items()
                       if r.get("cache") == "unwritten")
    cold = sorted(n for n, r in first.items() if r.get("cache") == "miss"
                  and second[n]["cache"] != "hit")
    assert not cold, (
        f"compiled again on a second build against the same cache: {cold}",
        first, second)
    return {"setup_s": setup_s, "run_s": time.perf_counter() - t0,
            "decode_attention": kv["decode_attention"],
            "sampler": kv["sampler"],
            "first_calls_s": first_calls, "second_calls_s": second_calls,
            "unwritten": unwritten, "first": first, "second": second}


def phase_sambay_kernel(*, slots=32, n_heads=40, kv_heads=10, head_dim=128,
                        page_size=16, capacity=6144, lengths=(1024, 4400),
                        reads=8, interpret=False):
    """:func:`phase_pages_kernel` for two pools of token rows
    (``ops.paged_attention.paged_attention_rows`` against
    ``ops.attention.gathered_rows_decode_attention``) at the agent
    cell's shapes: ``models/sambay.py``'s one-layer pool, a token's 10
    paired KV heads of 128 end to end, 40 query heads, ``slots`` slots
    whose lengths are drawn from ``lengths`` (both ends among them).
    Also times the kernel alone: ``kernel_ms`` is one of the decode
    step's ``reads`` walks, ``memory_speed_share`` the live K and V
    bytes over that time against the chip's 819 GB/s."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.ops.attention import gathered_rows_decode_attention
    from mxtpu.ops.paged_attention import paged_attention_rows

    t0 = time.perf_counter()
    per_slot = capacity // page_size
    n_pages = 1 + slots * per_slot
    rng = np.random.default_rng(0)
    lens = rng.integers(lengths[0], lengths[1] + 1, slots).astype(np.int32)
    lens[:3] = lengths[0], lengths[0] + page_size + 1, lengths[1]
    table = (1 + rng.permutation(n_pages - 1)).astype(np.int32).reshape(
        slots, per_slot)
    shape = (1, n_pages, page_size, kv_heads * head_dim)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16))
    kp, vp = pool(keys[0]), pool(keys[1])
    q = jax.random.normal(keys[2], (slots, n_heads, 1, head_dim),
                          jnp.bfloat16)
    scale = (head_dim // 2) ** -0.5             # a paired head is two
    kernel = jax.jit(lambda *a: paged_attention_rows(
        *a, layer=0, scale=scale, interpret=interpret))
    gathered = jax.jit(lambda *a: gathered_rows_decode_attention(
        *a, layer=0, scale=scale))
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(lens))
    got = np.asarray(kernel(*args), np.float32)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = np.asarray(gathered(*args), np.float32)
    worst, largest = float(np.abs(got - want).max()), float(
        np.abs(want).max())
    assert np.isfinite(got).all()
    assert worst <= 4 * 2.0 ** -8 * max(1.0, largest), (worst, largest)

    @jax.jit
    def step(q, *rest):         # the decode step's chain of reads
        for _ in range(reads):
            q = q + paged_attention_rows(q, *rest, layer=0, scale=scale,
                                         interpret=interpret)
        return q
    step(*args).block_until_ready()
    t1 = time.perf_counter()
    rounds = 1 if interpret else 20
    for _ in range(rounds):
        out = step(*args)
    out.block_until_ready()
    kernel_ms = (time.perf_counter() - t1) * 1e3 / rounds / reads
    live_bytes = 2 * int(lens.sum()) * kv_heads * head_dim * 2
    return {"setup_s": setup_s, "run_s": time.perf_counter() - t0,
            "slots": slots, "lengths": [int(lens.min()), int(lens.max())],
            "pool_bytes": 2 * int(np.prod(shape)) * 2,
            "largest_difference": worst, "largest_output": largest,
            "kernel_ms": kernel_ms, "live_bytes": live_bytes,
            "memory_speed_share": live_bytes / (kernel_ms * 1e-3) / 819e9}


def phase_serve_family(cfg, jobs, *, tol_f32=1e-3, expect_attention=None,
                       expect_attention_f32="gathered", expect_sampler=None,
                       **engine_kw):
    """A serving family other than llama (``models.serving_family(cfg)``:
    ``sambay.py``'s state-space, window, full, GMU and cross-attention
    layers; ``latent_moe.py``'s latent attention and routed experts;
    ``retention.py``'s power-retention layers, whose pool has no pages;
    ``blockdiff_moe.py``'s blocks of diffusion, whose streams are
    replayed pass by pass: ``_block_replay_gap``)
    through a paged ``ServeEngine`` behind ``Gateway.start_http``, once
    in the config's bf16 and once in float32 at ``highest`` precision,
    against its own ``forward``: ``jobs`` are asked greedily, and each
    emitted token's logit in one ``forward`` over prompt + stream is
    held against that position's largest. In float32 the two must agree
    (gap under ``tol_f32``); in bf16 the worst gap is reported (a
    near-tie may flip, as it does for llama). ``expect_attention`` is
    what the engine must say the first pass's decode program reads its
    pool through (``"pages"`` on the chip: a bf16 pool the family's
    kernel takes as stored); the float32 pass gathers everywhere
    (``expect_attention_f32``; a retention step reads no pool and says
    ``"state"`` in both); ``expect_sampler`` is what it must say of its
    sampler's threshold search (``"search_kernel"`` on the chip for a
    bank of eight slots: the kernel is in the decode program at the
    family's own vocabulary; a greedy row asks it for nothing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.models import serving_family
    from mxtpu.serve import ServeEngine
    family = serving_family(cfg)
    from mxtpu.serve.gateway import Gateway, GatewayClient

    t0 = time.perf_counter()
    info = {"setup_s": 0.0, "run_s": 0.0, "requests": 2 * len(jobs)}
    for dtype, precision in ((cfg.dtype, None), (jnp.float32, "highest")):
        c = replace(cfg, dtype=dtype, param_dtype=dtype)
        name = np.dtype(dtype).name
        with _matmul_precision(precision):
            params = jax.jit(lambda k: family.init_params(c, k))(
                jax.random.PRNGKey(0))
            gw = Gateway(lambda: ServeEngine(c, params, **engine_kw),
                         n_replicas=1, queue_max=4 * len(jobs),
                         supervisor_opts={"stall_s": 900.0,
                                          "warmup_s": 900.0})
            results = [None] * len(jobs)
            try:
                port = gw.start_http(port=0)
                engine = gw.backend.replicas()[0].engine

                def ask(i):
                    results[i] = GatewayClient(
                        "127.0.0.1", port, timeout=900).generate(
                            jobs[i]["prompt"], jobs[i]["mnew"],
                            seed=jobs[i]["seed"], temperature=0.0)
                ask(0)                   # compiles ahead of the others
                info["setup_s"] += time.perf_counter() - t0
                t0 = time.perf_counter()
                threads = [threading.Thread(target=ask, args=(i,))
                           for i in range(len(jobs))]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(900)
                assert not any(t.is_alive() for t in threads), "a client hung"
                assert engine.compile_count == 1 + engine.n_buckets
                kv = engine.kv_cache_stats()
                assert kv["reserved_bytes"] > 0, kv
                info[f"decode_attention_{name}"] = kv["decode_attention"]
                info["sampler"] = kv["sampler"]
                if "prefill_window_attention" in kv:
                    info[f"prefill_window_attention_{name}"] = \
                        kv["prefill_window_attention"]
            finally:
                gw.close()
            fwd = jax.jit(lambda p, t: family.forward(c, p, t))
            worst = 0.0
            for job, rec in zip(jobs, results):
                assert rec is not None and rec["status"] == 200 and \
                    len(rec["tokens"]) == job["mnew"], rec
                if hasattr(family, "block_step_slots_paged"):
                    worst = max(worst, _block_replay_gap(
                        c, fwd, params, job["prompt"], rec["tokens"]))
                    continue
                n0 = len(job["prompt"])
                seq = job["prompt"] + rec["tokens"]
                seq = seq + [0] * (-len(seq) % 128)
                lg = fwd(params, jnp.asarray(seq, jnp.int32)[None])[0]
                lg = np.asarray(lg[n0 - 1:n0 - 1 + job["mnew"]])
                took = lg[np.arange(job["mnew"]), rec["tokens"]]
                worst = max(worst, float((lg.max(-1) - took).max()))
            info[f"worst_gap_{name}"] = worst
            info["run_s"] += time.perf_counter() - t0
            t0 = time.perf_counter()
    assert info["worst_gap_float32"] <= tol_f32, info
    assert info["decode_attention_float32"] == expect_attention_f32, info
    assert expect_sampler in (None, info["sampler"]), info
    assert expect_attention in (
        None, info[f"decode_attention_{np.dtype(cfg.dtype).name}"]), info
    return info


def _block_replay_gap(cfg, fwd, params, prompt, tokens):
    """A block-diffusion stream against the family's own ``forward`` (no
    cache, no pages): the blocks are replayed pass by pass, teacher-forced
    with the tokens the engine emitted. A pass feeds the block as it
    then stands (``[MASK]`` where not yet filled) behind the prefix; the
    ``per_pass`` most confident masked positions take the emitted
    tokens, each of which lies ``gap`` under its position's largest
    logit. Returns the worst gap (0 where the engine took the argmax of
    these logits at every fill, in this order)."""
    import jax.numpy as jnp
    import numpy as np
    B, mask_id = cfg.block_length, cfg.mask_token_id
    seq, done, worst = list(prompt), 0, 0.0
    start = len(seq) // B * B
    pad = -(-(len(prompt) + len(tokens) + B) // 128) * 128
    while done < len(tokens):
        block = seq[start:]
        masked = list(range(len(block), B))
        want = dict(zip(masked, tokens[done:done + len(masked)]))
        block = block + [mask_id] * len(masked)
        while masked:
            fed = seq[:start] + block
            lg = np.array(fwd(params, jnp.asarray(
                fed + [0] * (pad - len(fed)), jnp.int32)[None])[
                    0, start:start + B], np.float64)
            lg[:, mask_id] = -np.inf
            top = lg.max(-1)
            conf = 1.0 / np.exp(lg - top[:, None]).sum(-1)
            for i in sorted(masked, key=lambda i: (-conf[i], i))[
                    :cfg.per_pass]:
                # the last block's positions past the request's count
                # were drawn and cut: the replay's own candidate there
                block[i] = want.get(i, int(lg[i].argmax()))
                worst = max(worst, float(top[i] - lg[i][block[i]]))
                masked.remove(i)
        seq, done, start = seq[:start] + block, done + len(want), start + B
    return worst


# -- the run ----------------------------------------------------------------
def _run(name, fn, *args, **kw):
    """One phase: PASS line with its facts, or FAIL and a non-zero
    exit."""
    print(f"---- {name}", flush=True)
    try:
        out = fn(*args, **kw)
    except BaseException:
        traceback.print_exc()
        print(f"FAIL {name}", flush=True)
        raise SystemExit(1)
    info, rest = out if isinstance(out, tuple) else (out, None)
    facts = " ".join(f"{k}={json.dumps(v)}" for k, v in info.items()
                     if k not in ("setup_s", "run_s"))
    print(f"PASS {name} setup_s={info['setup_s']:.1f} "
          f"run_s={info['run_s']:.1f} {facts}", flush=True)
    return rest


def main():
    # a hang must end inside the driver's limit, with every stack shown
    faulthandler.dump_traceback_later(1150, exit=True)
    import jax
    import jax.numpy as jnp
    import mxtpu as mx
    from mxtpu import runtime
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh

    cache_dir = runtime.use_compile_cache()     # before anything compiles
    _run("device", phase_device, cache_dir)
    _run("context", phase_context, mx.tpu())

    seq = 2048
    train_cfg = llama.LlamaConfig(
        **WIDTHS, max_seq_len=seq, attn_impl="flash", remat=True,
        remat_policy="dots_no_batch")
    _run("train", phase_train, train_cfg, 4, seq, 5)

    serve_cfg = llama.LlamaConfig(
        **WIDTHS, max_seq_len=SERVE_ENGINE["max_len"], remat=False)
    jobs = make_jobs(serve_cfg.vocab_size, SERVE_SHAPES)
    _run("pages_kernel", phase_pages_kernel)
    _run("sampler_search", phase_sampler_search)
    _run("serve_warm_setup", phase_serve_warm_setup,
         llama.LlamaConfig(**WARM_SETUP_WIDTHS,
                           max_seq_len=WARM_SETUP_ENGINE["max_len"],
                           remat=False), **WARM_SETUP_ENGINE)
    streams = _run("serve", phase_serve, serve_cfg, jobs, **SERVE_ENGINE,
                   expect_attention="pages", expect_sampler="search_kernel")
    _run("serve_f32", phase_serve,
         replace(serve_cfg, dtype=jnp.float32), jobs, **SERVE_ENGINE,
         precision="highest", must_match=True,
         expect_attention="gathered")

    # the second serving family, at its published widths and a small
    # depth (two Mamba+window pairs, layers "4/5", one GMU+cross pair):
    # prompts longer than three of its 512-token windows
    from mxtpu.models import sambay
    _run("sambay_kernel", phase_sambay_kernel)
    sambay_cfg = sambay.SambaYConfig(n_layers=8, max_seq_len=2048)
    _run("serve_sambay", phase_serve_family, sambay_cfg,
         make_jobs(sambay_cfg.vocab_size, SAMBAY_SHAPES, per_shape=2,
                   shared_prefix=0),
         max_slots=8, max_len=2048, min_bucket=256,
         expect_attention="pages", expect_sampler="search_kernel")

    # the third, at its published widths and a small depth (one dense
    # and two expert layers, all 128 experts): the longer prompt is
    # prefilled in two chunks, then absorbed decode over latent pages
    from mxtpu.models import latent_moe
    _run("latent_kernel", phase_latent_kernel)
    moe_cfg = latent_moe.LatentMoEConfig(n_layers=3, max_seq_len=2048)
    _run("serve_latent_moe", phase_serve_family, moe_cfg,
         make_jobs(moe_cfg.vocab_size, SAMBAY_SHAPES, per_shape=2,
                   shared_prefix=0),
         max_slots=8, max_len=2048, min_bucket=256, prefill_chunk=1024,
         expect_attention="pages", expect_sampler="search_kernel")

    # the fourth, at its published widths and a small depth (two
    # power-retention layers): the longer prompt is prefilled in two
    # chunks (the state handed on through the stage), then decode steps
    # over a fixed float32 state a slot, through the kernel in both
    # passes; the engine's pool has no pages
    from mxtpu.models import retention
    _run("retention_kernel", phase_retention_kernel)
    ret_cfg = retention.RetentionConfig(n_layers=2, max_seq_len=2048)
    _run("serve_retention", phase_serve_family, ret_cfg,
         make_jobs(ret_cfg.vocab_size, SAMBAY_SHAPES, per_shape=2,
                   shared_prefix=0),
         max_slots=8, max_len=2048, min_bucket=256, prefill_chunk=1024,
         expect_attention="state_kernel",
         expect_attention_f32="state_kernel",
         expect_sampler="search_kernel")

    # the fifth, at its published widths and a small depth (two
    # Qwen3-MoE layers, all 128 experts): the longer prompt is prefilled
    # in two chunks under the block-causal mask, then blocks of four are
    # denoised and committed, a step's 8 x 4 rows through the walk over
    # live pages; each stream is replayed against ``forward``
    from mxtpu.models import blockdiff_moe
    bd_cfg = blockdiff_moe.BlockDiffMoEConfig(n_layers=2, max_seq_len=2048)
    _run("serve_blockdiff", phase_serve_family, bd_cfg,
         make_jobs(bd_cfg.vocab_size, ((301, 16, 0.0), (1102, 12, 0.0)),
                   per_shape=2, shared_prefix=0),
         max_slots=8, max_len=2048, min_bucket=256, prefill_chunk=1024,
         expect_attention="pages", expect_sampler="search_kernel")

    if jax.device_count() >= 4:
        # the same two phases over a mesh with more than one
        # non-trivial axis: state spread, not parked on device 0
        _run("train_fsdp2_tp2", phase_train, train_cfg, 4, seq, 3,
             mesh_axes={"dp": -1, "fsdp": 2, "tp": 2})
        _run("train_fsdp2_sp2_ring", phase_train,
             replace(train_cfg, attn_impl="ring"), 4, seq, 3,
             mesh_axes={"dp": -1, "fsdp": 2, "sp": 2},
             expect_attn="ring")
        _run("serve_tp4", phase_serve, serve_cfg, jobs, **SERVE_ENGINE,
             mesh=pmesh.create_mesh(dp=-1, tp=4), compare_to=streams,
             expect_attention="gathered")

    faulthandler.cancel_dump_traceback_later()
    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
