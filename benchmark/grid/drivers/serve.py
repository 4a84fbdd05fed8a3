"""The serve driver: a paged ``ServeEngine`` behind
``Gateway.start_http``, loaded by the client child, measured from the
client's stamps.

Order of a run (everything before the window is set-up):
weights on the device from the seed -> gateway up -> one warm-up
request per prefill bucket the traffic can reach -> the check batch
against the plain reference (this decides half of ``correct``) ->
load starts, ``ramp_s`` later the window opens -> ``seconds`` later it
closes, the client shuts what is still open.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def client(plan: dict) -> list:
    """Run the client child on ``plan`` and return its records."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "client.py")],
        input=json.dumps(plan).encode(), stdout=subprocess.PIPE,
        timeout=3000)
    if proc.returncode != 0:
        raise RuntimeError(f"the client exited {proc.returncode}")
    return json.loads(proc.stdout)["records"]


def scrape(port: int) -> dict:
    """``GET /metrics`` -> {series name: sum over its label sets},
    the exposition's ``mxtpu_`` prefix taken off."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=60) as r:
        text = r.read().decode()
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0].removeprefix("mxtpu_")
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            pass
    return out


class PageSampler:
    """Peak page use over the window, from the engine's own host-side
    accounting (``kv_cache_stats`` syncs nothing)."""

    def __init__(self, engine, every_s: float = 0.05):
        self._engine, self._every = engine, every_s
        self._stop = threading.Event()
        self.peak_used = 0
        self.total = 0
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.wait(self._every):
            kv = self._engine.kv_cache_stats()
            self.peak_used = max(self.peak_used, kv.get("pages_used", 0))
            self.total = kv.get("pages_total", 0)

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(5.0)


def sleep_until(t: float):
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def check_batch(config, params, traffic, host, port, log):
    """Half of ``correct``: greedy requests through the gateway while
    nothing else runs, each emitted token held against the plain
    reference's logits at its position."""
    import numpy as np
    from reference import decoder
    check = config["check"]
    jobs = traffic.check_batch(check["n"], check["prompt_cap"],
                               check["new_tokens"])
    recs = client({"mode": "batch", "host": host, "port": port,
                   "jobs": jobs, "together": True})
    pad_to = check["prompt_cap"] + check["new_tokens"]
    worst, ok = 0.0, True
    for job, rec in zip(jobs, recs):
        if (rec["status"] != 200 or rec["reason"] != "complete"
                or len(rec["tokens"]) != check["new_tokens"]):
            log(f"# check request {job['id']} came back {rec['status']}"
                f" {rec['reason']} {rec['error']}")
            return False, float("nan")
        gaps = np.asarray(decoder.argmax_gaps(
            config, params, job["prompt"], rec["tokens"], pad_to))
        worst = max(worst, float(gaps.max()))
        ok = ok and bool(np.all(np.isfinite(gaps)))
        log(f"# check {job['id']}: prompt {len(job['prompt'])}, worst "
            f"gap {float(gaps.max()):.4f} at token {int(gaps.argmax())}")
    return ok and worst <= check["tol"], worst


def run(parts, device, seed, seconds, trace, t_process, log):
    import jax
    from functools import partial
    from mxtpu.models import llama
    from mxtpu.serve import ServeEngine
    from mxtpu.serve.engine import bucket_for
    from mxtpu.serve.gateway import Gateway
    import gen
    import stats
    import trace_reduce
    from program import llama_config, memory_peak, seed_key

    config, spec = parts["config"], parts["traffic"]
    eng, gwo = config["run"]["engine"], config["run"]["gateway"]
    check, vocab = config["check"], config["vocab_size"]
    chips = parts["cell"]["chips"]
    cfg = llama_config(config, config["run"])
    traffic = gen.Traffic(spec, seed, vocab)
    if traffic.max_total() > eng["max_len"]:
        raise SystemExit(f"traffic reaches {traffic.max_total()} tokens,"
                         f" the engine holds {eng['max_len']}")

    # weights: one jitted call on the device, in the stored type
    params = jax.jit(partial(llama.init_params, cfg))(seed_key(seed))
    gw = Gateway(
        lambda: ServeEngine(
            cfg, params, paged=True, max_slots=eng["max_slots"],
            max_len=eng["max_len"], min_bucket=eng["min_bucket"],
            page_size=eng["page_size"], n_pages=eng["n_pages"],
            prefix_cache=eng["prefix_cache"]),
        n_replicas=1, queue_max=gwo["queue_max"],
        supervisor_opts={"stall_s": gwo["stall_s"],
                         "warmup_s": gwo["warmup_s"]})
    try:
        host, port = "127.0.0.1", gw.start_http(port=0)
        engine = gw.backend.replicas()[0].engine

        # warm-up: the longest length of every bucket this traffic (and
        # the check batch) can reach; one short of a page boundary
        # where that keeps the bucket, so the last page is partial and
        # registering the prompt runs copy-page
        lengths = set(traffic.prefill_lengths())
        lengths |= {min(n, check["prompt_cap"]) for n in lengths}
        by_bucket: dict = {}
        for n in lengths:
            b = bucket_for(n, engine.min_bucket, engine.max_len)
            by_bucket[b] = max(by_bucket.get(b, 0), n)
        ps = eng["page_size"]
        warm = [n - 1 if n % ps == 0 and n > 1 and bucket_for(
                    n - 1, engine.min_bucket, engine.max_len) == b else n
                for b, n in sorted(by_bucket.items())]
        t0 = time.monotonic()
        recs = client({"mode": "batch", "host": host, "port": port,
                       "jobs": traffic.warmup(warm), "together": False})
        bad = [r for r in recs if r["status"] != 200
               or r["reason"] != "complete"]
        if bad:
            raise RuntimeError(f"warm-up failed: {bad[0]}")
        t_warm = time.monotonic() - t0

        t0 = time.monotonic()
        check_ok, worst = check_batch(config, params, traffic, host,
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
            target=lambda: box.update(records=client(plan)))
        loader.start()

        sleep_until(t_open)
        scrape0 = scrape(port)
        sampler = PageSampler(engine)
        sampler.start()
        if trace:
            with trace_reduce.profiled() as trace_dir:
                time.sleep(min(trace_reduce.TRACE_S, seconds / 2))
        sleep_until(t_close)
        scrape1 = scrape(port)
        sampler.stop()
        loader.join()
        records = box["records"]
        peak = memory_peak(jax.devices()[:chips])
    finally:
        gw.close()

    win = stats.serve_window(records, t_open, t_close, vocab, chips)
    log("# " + json.dumps({
        "check_ok": check_ok, "check_worst_gap": worst,
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
                  "requests_finished": win["finished"],
                  "n_ttft": win["n_ttft"],
                  "gen_lag_p95_ms": win["gen_lag_p95_ms"],
                  "ttft_p50_ms": win["ttft_p50_ms"],
                  "ttft_p95_ms": win["ttft_p95_ms"],
                  "itl_p50_ms": win["itl_p50_ms"]},
    }
    if trace:
        obs["reduced"] = trace_reduce.collect(trace_dir)
    return obs
