"""The load generator: a child process on plain sockets that never
imports JAX, so its threads share no interpreter lock with the engine
loop. A plan comes in on standard input as one JSON object, the
records go out on standard output as one JSON object.

  {"mode": "batch", "host", "port", "jobs": [request...],
   "together": bool}
      the jobs, all at once or one after another (warm-up, the check
      batch)
  {"mode": "load", "host", "port", "traffic": <traffic file>,
   "seed", "vocab", "t_start", "t_close", "grace_s"}
      the traffic's arrival process from ``t_start`` until ``t_close``
      (both on time.monotonic(), which every process of one Linux
      machine shares); ``grace_s`` after ``t_close`` every connection
      still open is shut, which cancels its request in the gateway.

A record: {"id", "due", "sent", "stamps": [receipt time of each
token], "status", "asked", "tokens", "reason", "error", "cut"}; a
request is timed from ``due`` — when it should have been sent — so a
late generator shows as latency, and ``sent - due`` is its lag.
"""
from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import gen  # noqa: E402  (numpy only)

_open = set()
_open_lock = threading.Lock()
_stop = threading.Event()


def ask(host, port, req, due):
    """One streamed request; every token's receipt is stamped."""
    body = {"prompt": req["prompt"],
            "max_new_tokens": req["max_new_tokens"],
            "temperature": req["temperature"], "seed": req["seed"],
            "stream": True}
    for k in ("top_p", "top_k"):
        if req.get(k) is not None:
            body[k] = req[k]
    body = json.dumps(body).encode()
    rec = {"id": req["id"], "due": due, "sent": None, "stamps": [],
           "status": 0, "asked": req["max_new_tokens"], "tokens": [],
           "reason": None, "error": None, "cut": False}
    s = None
    try:
        s = socket.create_connection((host, port), timeout=600)
        with _open_lock:
            _open.add(s)
        rec["sent"] = time.monotonic()
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
                now = time.monotonic()
                evt = json.loads(line)
                if evt.get("done"):
                    rec["reason"] = evt.get("reason")
                    rec["tokens"] = [int(t) for t in evt["tokens"]]
                    break
                rec["stamps"].append(now)
                rec["tokens"].append(int(evt["token"]))
        else:
            rec["error"] = f.read().decode(errors="replace")[:200]
        f.close()
    except (OSError, ValueError, IndexError) as e:
        # a connection this process shut at the end is a cut, not an
        # error of the system's
        if _stop.is_set():
            rec["cut"] = True
        else:
            rec["error"] = repr(e)
    finally:
        if s is not None:
            with _open_lock:
                _open.discard(s)
            s.close()
    if rec["reason"] is None and rec["error"] is None:
        rec["cut"] = True          # stream ended with no trailer
    return rec


def run_batch(plan):
    jobs, out = plan["jobs"], [None] * len(plan["jobs"])

    def one(i):
        out[i] = ask(plan["host"], plan["port"], jobs[i],
                     time.monotonic())
    if plan.get("together"):
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    else:
        for i in range(len(jobs)):
            one(i)
    return out


def run_load(plan):
    traffic = gen.Traffic(plan["traffic"], plan["seed"], plan["vocab"])
    host, port = plan["host"], plan["port"]
    t_start, t_close = plan["t_start"], plan["t_close"]
    arrival = plan["traffic"]["arrival"]
    records, lock = [], threading.Lock()

    def keep(rec):
        with lock:
            records.append(rec)

    def sleep_until(t):
        d = t - time.monotonic()
        if d > 0:
            time.sleep(d)

    threads = []
    if arrival["kind"] == "closed":
        n = int(arrival["callers"])

        def caller(c):
            due, k = t_start, 0
            while True:
                req = traffic.request(c, k, n)
                due += req["think_s"]
                if due >= t_close:
                    return
                sleep_until(due)
                keep(ask(host, port, req, due))
                if _stop.is_set():
                    return
                due, k = time.monotonic(), k + 1
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(n)]
    else:
        # open loop: request i of the schedule is due at its time
        # whatever the system does; one thread each, started on time
        dues = traffic.arrivals(t_close - t_start)

        def fire(i, due):
            sleep_until(due)
            keep(ask(host, port, traffic.request(i, 0, len(dues)), due))
        threads = [threading.Thread(target=fire, args=(i, t_start + d))
                   for i, d in enumerate(dues)]
    for t in threads:
        t.start()
    sleep_until(t_close + plan["grace_s"])
    _stop.set()
    with _open_lock:
        for s in list(_open):
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
    for t in threads:
        t.join()
    return records


def main():
    plan = json.load(sys.stdin)
    out = run_batch(plan) if plan["mode"] == "batch" else run_load(plan)
    json.dump({"records": out}, sys.stdout)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
