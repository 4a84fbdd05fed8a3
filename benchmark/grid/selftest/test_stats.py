"""Percentiles and window arithmetic on a record list written by
hand."""
import math

import stats


def rec(due, stamps, asked=None, **kw):
    out = {"id": "x", "due": due, "sent": due + 0.001, "stamps": stamps,
           "status": 200, "asked": asked or len(stamps),
           "tokens": list(range(len(stamps))), "reason": "complete",
           "error": None, "cut": False}
    out.update(kw)
    return out


def test_percentile_interpolates():
    assert stats.percentile([], 95) is None
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert math.isclose(stats.percentile(list(range(101)), 95), 95.0)
    assert math.isclose(stats.percentile([0.0, 10.0], 95), 9.5)


def test_serve_window_counts_only_what_the_window_holds():
    records = [
        # before the window: its first token is not evidence
        rec(8.0, [8.5, 9.0, 9.5]),
        # straddles the opening: TTFT before it, two gaps inside
        rec(9.0, [9.9, 10.1, 10.3]),
        # inside
        rec(10.0, [10.4, 10.5, 10.6, 10.7]),
        # cut at the end: neither a failure nor evidence of one
        rec(11.5, [11.8, 11.9], asked=50, reason=None, cut=True),
        # first token after the close
        rec(11.9, [12.1, 12.2], asked=2),
    ]
    w = stats.serve_window(records, 10.0, 12.0, vocab=100, chips=1)
    assert w["tokens"] == 2 + 4 + 2
    assert w["serve_tok_s"] == 8 / 2.0
    assert w["n_ttft"] == 2                   # 10.4-10.0 and 11.8-11.5
    assert math.isclose(w["ttft_p50_ms"], 350.0)
    assert w["n_gaps"] == 2 + 3 + 1
    assert w["attempted"] == 3 and w["failed"] == 0
    assert w["counts_ok"]
    assert math.isclose(w["gen_lag_p95_ms"], 1.0, abs_tol=1e-6)


def test_a_short_or_refused_request_fails_the_counts():
    good = rec(10.0, [10.1, 10.2])
    short = rec(10.0, [10.1], asked=2)                  # one token missing
    bad_id = rec(10.0, [10.1, 10.2], tokens=[1, 100])   # id == vocab
    shed = rec(10.0, [], asked=4, status=429, reason=None,
               error="overloaded")
    for bad in (short, bad_id, shed):
        w = stats.serve_window([good, bad], 10.0, 12.0, 100, 1)
        assert w["failed"] == 1 and not w["counts_ok"]
    w = stats.serve_window([good], 10.0, 12.0, 100, 2)
    assert w["counts_ok"] and w["serve_tok_s"] == 2 / 2.0 / 2


def test_train_window():
    losses = [5.0, 4.9, 4.8, 4.7, 3.0, 2.0, 1.0, 0.5]
    w = stats.train_window(losses, 100.0, 104.0, 1000, 4)
    assert w["train_tok_s"] == 8 * 1000 / 4.0 / 4
    assert w["counts_ok"] and w["attempted"] == 8 and w["failed"] == 0
    assert not stats.train_window(losses[::-1], 0.0, 1.0, 1, 1)["counts_ok"]
    nan = losses[:-1] + [float("nan")]
    w = stats.train_window(nan, 0.0, 1.0, 1, 1)
    assert not w["counts_ok"] and w["failed"] == 1
