"""The serve driver for a family named by the configuration: the
program's module, its config class and the plain reference all come
from the configuration file's ``family`` block

    "family": {"module": "mxtpu.models.<name>", "config": "<class>",
               "fields": {<config field>: <published key>, ..},
               "reference": "<file under reference/>"}

so a configuration of another serving family needs no driver of its own
(``drivers/serve.py`` and ``drivers/serve_sambay.py`` are this file
with llama's and sambay's names written in). The order of a run is
theirs — weights on the device from the seed -> gateway up -> one
warm-up request per prefill bucket the traffic can reach -> the check
batch against the plain reference -> load, ``ramp_s`` later the window
-> close — and the client, the scrape and the clock are
``drivers/serve.py``'s own, loaded from it. A cell of this kind is
judged by ``itl_p95_ms``; the serve metrics that move tokens/s ride in
the traced line's notes (``UNJUDGED``).

``check.also`` names further checks of what no emitted token shows
(``ALSO``), each with a limit of its own in ``check``.
"""
from __future__ import annotations

import importlib
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


# the accepted per-layer metrics that move tokens/s, which a cell of
# this kind does not report as judged numbers: read by their own
# readers into the traced line's notes
UNJUDGED = ("decode_batch_mean", "kv_pages_peak_share",
            "compiles_in_window.serve", "device_idle_share.serve",
            "engine_host_share")


def family_of(config: dict):
    """(the program's module, its config object, the reference's
    module) from the file's ``family`` block and ``run`` types."""
    import jax.numpy as jnp
    fam, run = config["family"], config["run"]
    module = importlib.import_module(fam["module"])
    cfg = getattr(module, fam["config"])(
        **{field: config[key] for field, key in fam["fields"].items()},
        dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]))
    return module, cfg, _load("reference", fam["reference"] + ".py")


class StateSampler(threading.Thread):
    """Peak page use and mean live tokens over the window, every 50 ms
    from the engine's own host-side accounting (``kv_cache_stats``
    syncs nothing)."""

    def __init__(self, engine, every_s: float = 0.05):
        super().__init__(daemon=True)
        self._engine, self._every = engine, every_s
        self._halt = threading.Event()
        self.peak_used = self.total = 0
        self._live = []

    def run(self):
        while not self._halt.wait(self._every):
            kv = self._engine.kv_cache_stats()
            self.peak_used = max(self.peak_used, kv.get("pages_used", 0))
            self.total = kv.get("pages_total", 0)
            rows = (self.total + 1) * kv["page_size"]
            paged = kv["reserved_bytes"] \
                - kv["state_bytes_per_slot"] * kv["slots"]
            live = kv["live_bytes"] \
                - kv["state_bytes_per_slot"] * kv["active"]
            self._live.append(live * rows / paged if paged else 0.0)

    def stop(self):
        self._halt.set()
        self.join(5.0)

    @property
    def live_tokens_mean(self):
        return sum(self._live) / len(self._live) if self._live else 0.0


def router_check(config, module, cfg, reference, params, seqs, seed, log):
    """What no emitted token shows for a routed family: the precision
    of the router's product, and how often near-tie picks part from the
    float32 reference's. (1) ``router_gap``: the program's router
    (``mxtpu.parallel.moe.route_sigmoid``) and the reference's
    (``route``) on the SAME inputs — ``check.prompt_cap`` rows of the
    activations' type from the seed, the last expert layer's router
    weights and bias — as the largest difference of any expert's
    weight; held to ``check.router_tol``. (2) ``router_flips``: over
    the check batch's sequences (prompt + emitted), the (token, expert
    layer) places where the program's picks in its own precision
    (``router_picks``) are not the reference's set; reported, and
    carried by ``check.tol``. Returns (ok, notes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.parallel import moe
    from program import seed_key
    check = config["check"]
    lp = params["moe"]
    w, bias = lp["router"][-1], lp["router_bias"][-1]
    x = jax.random.normal(jax.random.fold_in(seed_key(seed), 31),
                          (check["prompt_cap"], w.shape[0]), cfg.dtype)
    kw = dict(top_k=cfg.experts_per_tok, renorm=cfg.norm_topk_prob,
              scale=cfg.routed_scaling_factor)
    idx, wts = jax.jit(lambda x, w, b: moe.route_sigmoid(x, w, b, **kw))(
        x, w, bias)
    got = jnp.zeros((x.shape[0], w.shape[1]), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].set(wts)
    _, want = jax.jit(lambda x, w, b: reference.route(
        x.astype(jnp.float32), w.astype(jnp.float32), b,
        kw["top_k"], kw["renorm"], kw["scale"]))(x, w, bias)
    gap = float(jnp.abs(got - want).max())
    picks = jax.jit(lambda p, t: module.router_picks(cfg, p, t))
    by_layer, places = 0, 0
    for toks, ref_picks in seqs:
        mine = np.sort(np.asarray(picks(params, toks[None])), -1)
        theirs = np.sort(np.stack([np.asarray(p) for p in ref_picks]), -1)
        by_layer = by_layer + (mine != theirs).any(-1).sum(-1)
        places += mine.shape[0] * mine.shape[1]
    flips = int(by_layer.sum())
    log(f"# check router: weight gap {gap:.3g} (limit "
        f"{check['router_tol']}); {flips} of {places} (token, layer) "
        f"picks are not the float32 reference's, by layer "
        f"{by_layer.tolist()}")
    return gap <= check["router_tol"], {
        "check_router_gap": gap, "check_router_tol": check["router_tol"],
        "check_router_flips": flips, "check_router_places": places,
        "check_router_flips_by_layer": by_layer.tolist()}


def layers_check(config, module, cfg, reference, params, seqs, seed, log):
    """What a token cannot show where a model amplifies a rounding (a
    router's near tie decided otherwise changes a token's every later
    layer): each layer's OWN arithmetic. The program's pass over the
    check batch's first sequence hands out the stream entering every
    layer (``layer_streams``, the activations' type); the reference's
    layer (float32, decompressed attention, every expert on every
    token) is applied to each and held against the stream the program
    got out of it, a token at a time: ``|got - want| / |want - in|``,
    the error over the layer's own contribution. The MEDIAN over the
    tokens (a token whose router pick parts from the reference's reads
    a whole expert's output, and about one in twenty does) and the
    largest over the layers is held to ``check.layer_tol``. Returns
    (ok, notes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    del seed
    toks = seqs[0][0]
    streams = jax.jit(lambda p, t: module.layer_streams(cfg, p, t))(
        params, toks[None])[:, 0].astype(jnp.float32)
    gaps = []
    for index in range(config["num_hidden_layers"]):
        want = reference.layer(config, params, index, streams[index])
        err = jnp.linalg.norm(streams[index + 1] - want, axis=-1)
        own = jnp.linalg.norm(want - streams[index], axis=-1)
        gaps.append(float(jnp.median(err / own)))
    gap = max(gaps)
    log(f"# check layers: median error over a layer's own output, by "
        f"layer {np.round(gaps, 4).tolist()} (limit {config['check']['layer_tol']})")
    return gap <= config["check"]["layer_tol"], {
        "check_layer_gap": gap,
        "check_layer_tol": config["check"]["layer_tol"],
        "check_layer_gaps": gaps}


ALSO = {"router": router_check, "layers": layers_check}


def check_batch(serve, config, module, cfg, reference, params, traffic,
                host, port, log):
    """Half of ``correct``: greedy requests through the gateway while
    nothing else runs, each emitted token held against the plain
    reference's logits at its position (``check.tol``); then the checks
    ``check.also`` names. Returns (ok, worst token gap, notes)."""
    import jax.numpy as jnp
    import numpy as np
    check = config["check"]
    jobs = traffic.check_batch(check["n"], check["prompt_cap"],
                               check["new_tokens"])
    recs = serve.client({"mode": "batch", "host": host, "port": port,
                         "jobs": jobs, "together": True})
    pad_to = check["prompt_cap"] + check["new_tokens"]
    worst, ok, seqs = 0.0, True, []
    for job, rec in zip(jobs, recs):
        if (rec["status"] != 200 or rec["reason"] != "complete"
                or len(rec["tokens"]) != check["new_tokens"]):
            log(f"# check request {job['id']} came back {rec['status']}"
                f" {rec['reason']} {rec['error']}")
            return False, float("nan"), {}
        picks = []
        gaps = np.asarray(reference.argmax_gaps(
            config, params, job["prompt"], rec["tokens"], pad_to,
            picks=picks))
        seq = job["prompt"] + rec["tokens"]
        seqs.append((jnp.asarray(seq + [0] * (pad_to - len(seq)),
                                 jnp.int32), picks))
        worst = max(worst, float(gaps.max()))
        ok = ok and bool(np.all(np.isfinite(gaps)))
        log(f"# check {job['id']}: prompt {len(job['prompt'])}, worst "
            f"gap {float(gaps.max()):.4f} at token {int(gaps.argmax())}")
    ok, notes = ok and worst <= check["tol"], {}
    for name in check.get("also", ()):
        fine, more = ALSO[name](config, module, cfg, reference, params,
                                seqs, traffic.seed, log)
        ok = ok and fine
        notes.update(more)
    return ok, worst, notes


def run(parts, device, seed, seconds, trace, t_process, log):
    import jax
    from functools import partial
    from mxtpu.serve import ServeEngine
    from mxtpu.serve.engine import bucket_for
    from mxtpu.serve.gateway import Gateway
    import gen
    import stats
    import trace_reduce
    from program import memory_peak, seed_key
    from program_reads import hist_sum

    serve = _load("drivers", "serve.py")
    config, spec = parts["config"], parts["traffic"]
    module, cfg, reference = family_of(config)
    eng, gwo = config["run"]["engine"], config["run"]["gateway"]
    check, vocab = config["check"], config["vocab_size"]
    chips = parts["cell"]["chips"]
    traffic = gen.Traffic(spec, seed, vocab)
    if traffic.max_total() > eng["max_len"]:
        raise SystemExit(f"traffic reaches {traffic.max_total()} tokens,"
                         f" the engine holds {eng['max_len']}")

    # weights: one jitted call on the device, in the stored type
    params = jax.jit(partial(module.init_params, cfg))(seed_key(seed))
    gw = Gateway(
        lambda: ServeEngine(
            cfg, params, max_slots=eng["max_slots"],
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
        check_ok, worst, check_notes = check_batch(
            serve, config, module, cfg, reference, params, traffic, host,
            port, log)
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
        sampler = StateSampler(engine)
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
        "check_ok": check_ok, "check_worst_gap": worst, **check_notes,
        "warm_s": t_warm, "check_s": t_check, "buckets": sorted(by_bucket),
        **win}))
    obs = {
        "correct": check_ok and win["counts_ok"],
        "attempted": win["attempted"], "failed": win["failed"],
        "memory_peak_bytes": peak,
        # every number the window gives can be named as a metric
        "end_to_end": dict(win, setup_s=t_open - t_process),
        "scrape0": scrape0, "scrape1": scrape1,
        "pages": {"peak_used": sampler.peak_used, "total": sampler.total,
                  "live_tokens_mean": sampler.live_tokens_mean},
        "window": win, "config": config, "traffic": spec,
        "device": device, "chips": chips, "seconds": seconds,
        "notes": {"check_worst_gap": worst, "check_tol": check["tol"],
                  **check_notes,
                  "requests_finished": win["finished"],
                  "n_ttft": win["n_ttft"],
                  "gen_lag_p95_ms": win["gen_lag_p95_ms"],
                  "ttft_p50_ms": win["ttft_p50_ms"],
                  "ttft_p95_ms": win["ttft_p95_ms"],
                  "itl_p50_ms": win["itl_p50_ms"],
                  # not judged in a cell of this kind: which prompts of
                  # 1-4k tokens a window admits moves the tokens it
                  # completes by more than a bound holds
                  "serve_tok_s": win["serve_tok_s"],
                  "live_tokens_mean": sampler.live_tokens_mean,
                  "state_reserved_bytes": state["reserved_bytes"],
                  "decode_attention": state["decode_attention"]},
    }
    # of the window's decode steps (each ends a gap of every running
    # request), the share that had a prefill chunk in front of them, and
    # the share whose chunk was a prompt's last
    steps = scrape1.get("serve_steps_total", 0.0) \
        - scrape0.get("serve_steps_total", 0.0)
    for note, series in (("gaps_with_chunk_share", "span_serve_prefill_ms"),
                         ("gaps_after_last_chunk_share",
                          "serve_ttft_first_wait_ms")):
        n = hist_sum(obs, series, "_count")
        if steps and n is not None:
            obs["notes"][note] = 100.0 * n / steps
    if trace:
        obs["reduced"] = trace_reduce.collect(trace_dir)
        for name in UNJUDGED:
            value = _load("readers", name + ".py").read(obs)
            if value is not None:
                obs["notes"][name] = float(value)
    return obs
