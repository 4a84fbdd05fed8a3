"""The one traffic generator: a traffic file's parameters and a seed in,
requests out. numpy only — the client child imports this and must never
import JAX.

Every seed gets the SAME multiset of sizes: the length laws are turned
into a fixed pool of ``pool`` (prompt length, output length) pairs by
their quantiles, and the seed only permutes the pool's order round by
round and draws the token ids. So two seeds differ in order and
content, never in the amount of work.

A traffic file (``traffic/<name>.json``) holds:

  arrival   {"kind": "closed", "callers": n}
            {"kind": "poisson", "rate": r}            requests/s
            {"kind": "gamma", "rate": r, "cv": c}     burstiness CV c
  requests  {"kind": "independent"}
            {"kind": "session", "turns": t, "prefix": <law>,
             "think_s": <law>}   one shared prefix per session, asked
                                 ``turns`` times with a fresh prompt
  prompt    <law>   tokens (for a session: the fresh part of each turn)
  output    <law>   new tokens asked for
  pool      how many (prompt, output) pairs make one round
  sampling  {"temperature": t, "top_p": p, "top_k": k}  (all optional)
  ramp_s    seconds of load before the window opens (set-up)

A law is {"law": "fixed", "value": v} | {"law": "uniform", "lo", "hi"}
| {"law": "lognormal", "median", "sigma", "lo", "hi"} (clipped).
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

_POOL_SEED = 0x6772_6964          # "grid": the pool never depends on --seed


def quantile(law: Dict[str, Any], u: float) -> float:
    """The law's value at quantile ``u`` in (0, 1)."""
    kind = law["law"]
    if kind == "fixed":
        return float(law["value"])
    if kind == "uniform":
        return law["lo"] + u * (law["hi"] - law["lo"])
    if kind == "lognormal":
        x = law["median"] * math.exp(law["sigma"] * NormalDist().inv_cdf(u))
        return min(max(x, law["lo"]), law["hi"])
    raise ValueError(f"unknown length law {kind!r}")


def law_pool(law: Dict[str, Any], n: int, salt: int,
             integer: bool = True) -> np.ndarray:
    """``n`` values at the law's evenly spaced quantiles, in an order
    fixed by ``salt`` alone."""
    vals = np.array([quantile(law, (i + 0.5) / n) for i in range(n)])
    if integer:
        vals = np.rint(vals).astype(np.int64)
    order = np.random.default_rng([_POOL_SEED, salt]).permutation(n)
    return vals[order]


class Traffic:
    """Requests of one traffic file under one seed. A request is a
    dict ``{"id", "prompt", "max_new_tokens", "seed", "temperature",
    "top_p", "top_k", "think_s"}``; ``id`` is ``<stream>.<index>``."""

    def __init__(self, spec: Dict[str, Any], seed: int, vocab: int):
        self.spec = spec
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.n_pool = int(spec["pool"])
        self.kind = spec["requests"]["kind"]
        self.prompt_pool = law_pool(spec["prompt"], self.n_pool, 1)
        self.output_pool = law_pool(spec["output"], self.n_pool, 2)
        if self.kind == "session":
            r = spec["requests"]
            self.turns = int(r["turns"])
            self.prefix_pool = law_pool(r["prefix"], self.n_pool, 3)
            self.think_pool = law_pool(
                r.get("think_s", {"law": "fixed", "value": 0.0}),
                self.n_pool, 4, integer=False)
        elif self.kind != "independent":
            raise ValueError(f"unknown request kind {self.kind!r}")

    # -- sizes ---------------------------------------------------------
    def _slot(self, index: int) -> int:
        """Which pool entry the ``index``-th request of the run takes:
        each round of ``pool`` requests is one seeded permutation."""
        rnd, pos = divmod(index, self.n_pool)
        perm = np.random.default_rng(
            [self.seed, 11, rnd]).permutation(self.n_pool)
        return int(perm[pos])

    def _tokens(self, n: int, *key: int) -> List[int]:
        return np.random.default_rng([self.seed, *key]).integers(
            0, self.vocab, n).tolist()

    def max_total(self) -> int:
        """The most tokens one request can hold, prompt and output."""
        longest = int(self.prompt_pool.max()) + int(self.output_pool.max())
        if self.kind == "session":
            longest += int(self.prefix_pool.max())
        return longest

    # -- requests ------------------------------------------------------
    def request(self, stream: int, k: int, n_streams: int,
                stream_id: int = 0) -> Dict[str, Any]:
        """The ``k``-th request of stream (caller) ``stream`` out of
        ``n_streams``. ``stream_id`` sets apart streams of one run that
        must not repeat each other (the check batch, the warm-up)."""
        index = k * n_streams + stream
        slot = self._slot(index)
        samp = self.spec.get("sampling", {})
        req = {
            "id": f"{stream_id}.{stream}.{k}",
            "max_new_tokens": int(self.output_pool[slot]),
            # below 2**31 whatever --seed is: the engine makes a
            # PRNGKey of it
            "seed": int(np.random.default_rng(
                [self.seed, 12, stream_id, stream, k]).integers(
                    0, 2 ** 31 - 1)),
            "temperature": float(samp.get("temperature", 0.0)),
            "top_p": samp.get("top_p"),
            "top_k": samp.get("top_k"),
            "think_s": 0.0,
        }
        fresh = self._tokens(int(self.prompt_pool[slot]),
                             13, stream_id, stream, k)
        if self.kind == "independent":
            req["prompt"] = fresh
            return req
        # session: requests k = s*turns .. s*turns + turns-1 of a
        # stream share session s's prefix
        sess, turn = divmod(k, self.turns)
        sslot = self._slot(sess * n_streams + stream)
        prefix = self._tokens(int(self.prefix_pool[sslot]),
                              14, stream_id, stream, sess)
        req["prompt"] = prefix + fresh
        req["shared"] = len(prefix) if turn else 0
        req["think_s"] = float(self.think_pool[sslot]) if turn else 0.0
        return req

    def arrivals(self, horizon_s: float) -> List[float]:
        """Due times (seconds from the start of load) of an open loop,
        up to ``horizon_s``: exponential gaps for ``poisson``, gamma
        gaps with coefficient of variation ``cv`` for ``gamma``."""
        arr = self.spec["arrival"]
        rate = float(arr["rate"])
        rng = np.random.default_rng([self.seed, 15])
        n = max(16, int(rate * horizon_s * 1.5) + 16)
        if arr["kind"] == "poisson":
            gaps = rng.exponential(1.0 / rate, n)
        elif arr["kind"] == "gamma":
            shape = 1.0 / float(arr["cv"]) ** 2
            gaps = rng.gamma(shape, 1.0 / (rate * shape), n)
        else:
            raise ValueError(
                f"arrival {arr['kind']!r} has no schedule")
        due = np.cumsum(gaps)
        return [float(t) for t in due[due < horizon_s]]

    def check_batch(self, n: int, prompt_cap: int,
                    new_tokens: int) -> List[Dict[str, Any]]:
        """The fixed batch ``correct`` is decided on: ``n`` greedy
        requests from a stream of their own, prompts cut to
        ``prompt_cap``, ``new_tokens`` each. A session traffic's batch
        is one session's first turns, so it checks the warm path."""
        out = []
        for k in range(n):
            req = self.request(0, k, 1, stream_id=1)
            req.update(prompt=req["prompt"][:prompt_cap],
                       max_new_tokens=new_tokens, temperature=0.0,
                       top_p=None, top_k=None, think_s=0.0)
            out.append(req)
        return out

    def prefill_lengths(self) -> List[int]:
        """Every token count a prefill of this traffic can run over:
        whole prompts (cold) and, for sessions, the fresh part behind a
        shared prefix (the prefix-hit path). The driver maps them to
        the engine's buckets and warms each bucket once."""
        fresh = {int(p) for p in self.prompt_pool}
        if self.kind == "independent":
            return sorted(fresh)
        whole = {int(a) + int(b) for a in self.prefix_pool
                 for b in self.prompt_pool}
        return sorted(fresh | whole)

    def warmup(self, lengths: List[int]) -> List[Dict[str, Any]]:
        """One 2-token request per length in ``lengths`` (the driver
        gives one length per prefill bucket). For session traffic the
        last prompt is then asked again with a fresh tail of the
        shortest length, so the prefix-hit path (copy-on-write fork,
        suffix prefill) has run once too."""
        out = []
        for i, n in enumerate(lengths):
            out.append(dict(self.request(0, i, 1, stream_id=2),
                            prompt=self._tokens(n, 16, i),
                            max_new_tokens=2, think_s=0.0))
        if self.kind == "session" and out:
            tail = min(lengths)
            head = out[-1]["prompt"][:max(1, max(lengths) - tail)]
            out.append(dict(self.request(0, len(lengths), 1, stream_id=2),
                            prompt=head + self._tokens(tail, 17),
                            max_new_tokens=2, think_s=0.0))
        return out
