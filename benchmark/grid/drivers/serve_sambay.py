"""The serve driver for the SambaY family (``mxtpu/models/sambay.py``;
Phi-4-mini-flash-reasoning): the order of a run is ``drivers/serve.py``'s
— weights on the device from the seed -> gateway up -> one warm-up
request per prefill bucket the traffic can reach -> the check batch
against the family's plain reference (``reference/sambay.py``) ->
load, ``ramp_s`` later the window -> close — with the family's config
object, weights and reference in the places where that file names
llama's. The client, the scrape, the page sampler and the clock are
``drivers/serve.py``'s own, loaded from it.
"""
from __future__ import annotations

import importlib.util
import json
import os
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(*path):
    """A file beside this one as a module (the directories are no
    packages, and a reader's name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "grid_" + "_".join(path).replace(".", "_"),
        os.path.join(os.path.dirname(HERE), *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _serve():
    """``drivers/serve.py``: the client, the scrape, the page sampler
    and the clock are its own."""
    return _load("drivers", "serve.py")


# the accepted per-layer metrics that move tokens/s, which this cell
# does not report: read by their own readers into the traced line's
# notes, so idle, host share and pool fill are seen for this family too
UNJUDGED = ("decode_batch_mean", "kv_pages_peak_share",
            "compiles_in_window.serve", "device_idle_share.serve",
            "engine_host_share")


def sambay_config(model: dict, run: dict):
    """The program's config object from the file's published keys;
    what the file lists under ``assumed`` is the config class's
    defaults."""
    import jax.numpy as jnp
    from mxtpu.models import sambay
    return sambay.SambaYConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        hidden_dim=model["intermediate_size"],
        sliding_window=model["sliding_window"],
        mb_per_layer=model["mb_per_layer"],
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["layer_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]))


def scan_gap(config, cfg, params, seed):
    """What no emitted token shows (PERF.md section 6, PR 27): the
    precision the recurrent state is held in. The program's own scan
    (``mxtpu.ops.ssm.selective_scan``, in the chunks the prefill runs
    it in) and the plain reference's recurrence on the SAME inputs:
    one sequence of ``check.prompt_cap`` steps at the configuration's
    ``d_inner x d_state``, inputs from the seed in the activations'
    type, step sizes and ``A`` from the last Mamba layer's weights.
    Returns ``|s - s_ref| / |s_ref|`` (Frobenius) of the state after
    the last step."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import ssm
    from program import seed_key
    from reference import sambay as reference
    s, f32 = config["check"]["prompt_cap"], jnp.float32
    lp = params["mid"]["mamba"]
    ku, kr, kb, kc = jax.random.split(
        jax.random.fold_in(seed_key(seed), 27), 4)
    u = jax.random.normal(ku, (1, s, cfg.d_inner), cfg.dtype)
    B = jax.random.normal(kb, (1, s, cfg.d_state), cfg.dtype)
    C = jax.random.normal(kc, (1, s, cfg.d_state), cfg.dtype)
    dt = jax.nn.softplus(jax.random.normal(kr, u.shape, f32)
                         + lp["dt_bias"].astype(f32))
    A = -jnp.exp(lp["A_log"].astype(f32))
    _, got = jax.jit(ssm.selective_scan, static_argnames="chunk")(
        u, dt, A, B, C, lp["D"], jnp.zeros((1,) + A.shape, f32),
        chunk=cfg.scan_chunk)
    want, _ = jax.jit(reference.selective_scan)(
        dt[0], u[0].astype(f32), A, B[0].astype(f32), C[0].astype(f32))
    return float(jnp.linalg.norm(got[0] - want) / jnp.linalg.norm(want))


def check_batch(serve, config, cfg, params, traffic, host, port, log):
    """Half of ``correct``: greedy requests through the gateway while
    nothing else runs, each emitted token held against the plain
    reference's logits at its position (``check.tol``); then the
    program's scan against the reference's recurrence
    (:func:`scan_gap`, ``check.scan_tol``). Returns (ok, worst token
    gap, scan gap)."""
    import numpy as np
    from reference import sambay as reference
    check = config["check"]
    jobs = traffic.check_batch(check["n"], check["prompt_cap"],
                               check["new_tokens"])
    recs = serve.client({"mode": "batch", "host": host, "port": port,
                         "jobs": jobs, "together": True})
    pad_to = check["prompt_cap"] + check["new_tokens"]
    worst, ok = 0.0, True
    for job, rec in zip(jobs, recs):
        if (rec["status"] != 200 or rec["reason"] != "complete"
                or len(rec["tokens"]) != check["new_tokens"]):
            log(f"# check request {job['id']} came back {rec['status']}"
                f" {rec['reason']} {rec['error']}")
            return False, float("nan"), float("nan")
        gaps = np.asarray(reference.argmax_gaps(
            config, params, job["prompt"], rec["tokens"], pad_to))
        worst = max(worst, float(gaps.max()))
        ok = ok and bool(np.all(np.isfinite(gaps)))
        log(f"# check {job['id']}: prompt {len(job['prompt'])}, worst "
            f"gap {float(gaps.max()):.4f} at token {int(gaps.argmax())}")
    scan = scan_gap(config, cfg, params, traffic.seed)
    log(f"# check scan: state after {check['prompt_cap']} steps, gap "
        f"{scan:.3g}")
    return ok and worst <= check["tol"] and scan <= check["scan_tol"], \
        worst, scan


def run(parts, device, seed, seconds, trace, t_process, log):
    import jax
    from functools import partial
    from mxtpu.models import sambay
    from mxtpu.serve import ServeEngine
    from mxtpu.serve.engine import bucket_for
    from mxtpu.serve.gateway import Gateway
    import gen
    import stats
    import trace_reduce
    from program import memory_peak, seed_key

    serve = _serve()
    config, spec = parts["config"], parts["traffic"]
    eng, gwo = config["run"]["engine"], config["run"]["gateway"]
    check, vocab = config["check"], config["vocab_size"]
    chips = parts["cell"]["chips"]
    cfg = sambay_config(config, config["run"])
    traffic = gen.Traffic(spec, seed, vocab)
    if traffic.max_total() > eng["max_len"]:
        raise SystemExit(f"traffic reaches {traffic.max_total()} tokens,"
                         f" the engine holds {eng['max_len']}")

    # weights: one jitted call on the device, in the stored type
    params = jax.jit(partial(sambay.init_params, cfg))(seed_key(seed))
    gw = Gateway(
        lambda: ServeEngine(
            cfg, params, paged=True, max_slots=eng["max_slots"],
            max_len=eng["max_len"], min_bucket=eng["min_bucket"],
            page_size=eng["page_size"], n_pages=eng["n_pages"],
            prefix_cache=eng["prefix_cache"],
            prefill_chunk=eng.get("prefill_chunk")),
        n_replicas=1, queue_max=gwo["queue_max"],
        supervisor_opts={"stall_s": gwo["stall_s"],
                         "warmup_s": gwo["warmup_s"]})
    try:
        host, port = "127.0.0.1", gw.start_http(port=0)
        engine = gw.backend.replicas()[0].engine

        # warm-up: the longest length of every bucket this traffic (and
        # the check batch) can reach; an engine that prefills in chunks
        # has one bucket, the chunk, and the longest prompt runs both
        # of its programs
        lengths = set(traffic.prefill_lengths())
        lengths |= {min(n, check["prompt_cap"]) for n in lengths}
        by_bucket: dict = {}
        for n in lengths:
            b = engine.prefill_chunk or bucket_for(
                n, engine.min_bucket, engine.max_len)
            by_bucket[b] = max(by_bucket.get(b, 0), n)
        t0 = time.monotonic()
        recs = serve.client({
            "mode": "batch", "host": host, "port": port, "together": False,
            "jobs": traffic.warmup([n for _, n in sorted(by_bucket.items())])})
        bad = [r for r in recs if r["status"] != 200
               or r["reason"] != "complete"]
        if bad:
            raise RuntimeError(f"warm-up failed: {bad[0]}")
        t_warm = time.monotonic() - t0

        t0 = time.monotonic()
        check_ok, worst, scan = check_batch(
            serve, config, cfg, params, traffic, host, port, log)
        t_check = time.monotonic() - t0

        ramp = float(spec.get("ramp_s", 0.0))
        t_start = time.monotonic() + 0.5
        t_open = t_start + ramp
        t_close = t_open + seconds
        plan = {"mode": "load", "host": host, "port": port,
                "traffic": spec, "seed": seed, "vocab": vocab,
                "t_start": t_start, "t_close": t_close, "grace_s": 0.25}
        box: dict = {}
        loader = threading.Thread(
            target=lambda: box.update(records=serve.client(plan)))
        loader.start()

        serve.sleep_until(t_open)
        scrape0 = serve.scrape(port)
        sampler = serve.PageSampler(engine)
        sampler.start()
        if trace:
            with trace_reduce.profiled() as trace_dir:
                time.sleep(min(trace_reduce.TRACE_S, seconds / 2))
        serve.sleep_until(t_close)
        scrape1 = serve.scrape(port)
        sampler.stop()
        loader.join()
        records = box["records"]
        peak = memory_peak(jax.devices()[:chips])
        state = engine.kv_cache_stats()
    finally:
        gw.close()

    win = stats.serve_window(records, t_open, t_close, vocab, chips)
    log("# " + json.dumps({
        "check_ok": check_ok, "check_worst_gap": worst,
        "check_scan_gap": scan,
        "warm_s": t_warm, "check_s": t_check, "buckets": sorted(by_bucket),
        **win}))
    obs = {
        "correct": check_ok and win["counts_ok"],
        "attempted": win["attempted"], "failed": win["failed"],
        "memory_peak_bytes": peak,
        # every number the window gives can be named as a metric
        "end_to_end": dict(win, setup_s=t_open - t_process),
        "scrape0": scrape0, "scrape1": scrape1,
        "pages": {"peak_used": sampler.peak_used,
                  "total": sampler.total},
        "window": win, "config": config, "traffic": spec,
        "device": device, "chips": chips, "seconds": seconds,
        "notes": {"check_worst_gap": worst, "check_tol": check["tol"],
                  "check_scan_gap": scan,
                  "check_scan_tol": check["scan_tol"],
                  "requests_finished": win["finished"],
                  "n_ttft": win["n_ttft"],
                  "gen_lag_p95_ms": win["gen_lag_p95_ms"],
                  "ttft_p50_ms": win["ttft_p50_ms"],
                  "ttft_p95_ms": win["ttft_p95_ms"],
                  "itl_p50_ms": win["itl_p50_ms"],
                  # not judged in this family's cell: which 24-31
                  # prompts of 1-4k tokens a window admits moves the
                  # tokens it completes by more than a bound holds
                  "serve_tok_s": win["serve_tok_s"],
                  "state_reserved_bytes": state["reserved_bytes"],
                  "state_bytes_per_slot": state["state_bytes_per_slot"]},
    }
    if trace:
        obs["reduced"] = trace_reduce.collect(trace_dir)
        for name in UNJUDGED:
            value = _load("readers", name + ".py").read(obs)
            if value is not None:
                obs["notes"][name] = float(value)
    return obs
