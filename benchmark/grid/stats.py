"""Window arithmetic over the client's records: the reduction from
stamps to end-to-end metrics, and the counts that decide ``correct``
inside the window. Pure Python; no clock is read here."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by linear interpolation between order
    statistics (numpy's default). None for an empty list."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def finished(rec: Dict[str, Any]) -> bool:
    """The system said this request ended (a trailer came back)."""
    return rec["status"] == 200 and rec["reason"] is not None


def whole(rec: Dict[str, Any], vocab: int) -> bool:
    """A finished request carries exactly the tokens it asked for, all
    of them ids of the vocabulary."""
    return (rec["reason"] == "complete"
            and len(rec["tokens"]) == rec["asked"]
            and all(0 <= t < vocab for t in rec["tokens"]))


def refused(rec: Dict[str, Any]) -> bool:
    return rec["error"] is not None or (
        rec["status"] != 200 and not rec["cut"])


def serve_window(records: List[Dict[str, Any]], t_open: float,
                 t_close: float, vocab: int, chips: int
                 ) -> Dict[str, Any]:
    """What the window [t_open, t_close) shows. Requests cut off when
    the load ended are neither failures nor evidence."""
    def inside(t):
        return t is not None and t_open <= t < t_close

    tokens = sum(1 for r in records for t in r["stamps"] if inside(t))
    ttft = [1e3 * (r["stamps"][0] - r["due"]) for r in records
            if r["stamps"] and inside(r["stamps"][0])]
    gaps = [1e3 * (b - a) for r in records
            for a, b in zip(r["stamps"], r["stamps"][1:]) if inside(b)]
    lag = [1e3 * (r["sent"] - r["due"]) for r in records
           if inside(r["sent"])]
    sent = [r for r in records if inside(r["sent"])]
    failed = [r for r in sent if refused(r)
              or (finished(r) and not whole(r, vocab))]
    done = [r for r in records if finished(r)]
    return {
        "serve_tok_s": tokens / (t_close - t_open) / chips,
        "ttft_p90_ms": percentile(ttft, 90),
        "ttft_p95_ms": percentile(ttft, 95),
        "itl_p95_ms": percentile(gaps, 95),
        "ttft_p50_ms": percentile(ttft, 50),
        "itl_p50_ms": percentile(gaps, 50),
        "gen_lag_p95_ms": percentile(lag, 95),
        "n_ttft": len(ttft), "n_gaps": len(gaps), "tokens": tokens,
        "attempted": len(sent), "failed": len(failed),
        "finished": len(done),
        "finished_in_window": sum(
            1 for r in done if inside(r["stamps"][-1] if r["stamps"]
                                      else None)),
        "counts_ok": (not failed and bool(done)
                      and all(whole(r, vocab) for r in done)
                      and not any(refused(r) for r in records)),
    }


def train_window(losses: List[float], t_open: float, t_last: float,
                 tokens_per_step: int, chips: int) -> Dict[str, Any]:
    """Steps all start and end inside the window; the rate is taken to
    the last loss read back (the fence). The pool of batches is
    memorised, so the loss must fall."""
    n = len(losses)
    ok = (n >= 8 and all(math.isfinite(x) for x in losses)
          and sum(losses[-4:]) < sum(losses[:4]))
    return {
        "train_tok_s": n * tokens_per_step / (t_last - t_open) / chips,
        "step_s": (t_last - t_open) / n if n else None,
        "attempted": n,
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "counts_ok": ok,
    }
