"""The generator: the same seed gives the same requests, every seed
gets the same multiset of sizes, and every arrival process, request
kind and length law it takes as data works."""
import json
import os

import pytest

import gen

GRID = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAWS = [{"law": "fixed", "value": 7},
        {"law": "uniform", "lo": 4, "hi": 40},
        {"law": "lognormal", "median": 16, "sigma": 0.8, "lo": 4, "hi": 64}]
KINDS = [{"kind": "independent"},
         {"kind": "session", "turns": 3,
          "prefix": {"law": "uniform", "lo": 20, "hi": 30},
          "think_s": {"law": "fixed", "value": 0.25}}]
ARRIVALS = [{"kind": "closed", "callers": 4},
            {"kind": "poisson", "rate": 20.0},
            {"kind": "gamma", "rate": 20.0, "cv": 2.0}]


def spec(law, kind, arrival):
    return {"arrival": arrival, "requests": kind, "prompt": law,
            "output": law, "pool": 16,
            "sampling": {"temperature": 0.7, "top_p": 0.9}}


@pytest.mark.parametrize("law", LAWS, ids=lambda d: d["law"])
@pytest.mark.parametrize("kind", KINDS, ids=lambda d: d["kind"])
@pytest.mark.parametrize("arrival", ARRIVALS, ids=lambda d: d["kind"])
def test_same_seed_same_requests(law, kind, arrival):
    seed = 2 ** 31 + 12345          # more than 32 signed bits hold
    a = gen.Traffic(spec(law, kind, arrival), seed, 1000)
    b = gen.Traffic(spec(law, kind, arrival), seed, 1000)
    c = gen.Traffic(spec(law, kind, arrival), seed + 1, 1000)
    ra = [a.request(s, k, 4) for s in range(4) for k in range(8)]
    rb = [b.request(s, k, 4) for s in range(4) for k in range(8)]
    rc = [c.request(s, k, 4) for s in range(4) for k in range(8)]
    assert ra == rb
    assert [r["prompt"] for r in ra] != [r["prompt"] for r in rc]
    for r in ra:
        assert 0 <= r["seed"] < 2 ** 31
        assert all(0 <= t < 1000 for t in r["prompt"])
    # one round of the pool holds the same sizes whatever the seed
    assert (sorted(r["max_new_tokens"] for r in ra[:16])
            != [] and sorted(r["max_new_tokens"] for r in ra)
            == sorted(r["max_new_tokens"] for r in rc))
    if arrival["kind"] != "closed":
        due = a.arrivals(10.0)
        assert due == b.arrivals(10.0) and due == sorted(due)
        assert 100 < len(due) < 320 and due[-1] < 10.0


def test_law_quantiles_and_clips():
    pool = gen.law_pool(LAWS[2], 64, 1)
    assert pool.min() >= 4 and pool.max() <= 64
    assert abs(float(sorted(pool)[32]) - 16) <= 1
    assert set(gen.law_pool(LAWS[0], 8, 1)) == {7}
    with pytest.raises(ValueError):
        gen.quantile({"law": "zipf"}, 0.5)


def test_session_turns_share_their_prefix():
    t = gen.Traffic(spec(LAWS[1], KINDS[1], ARRIVALS[0]), 5, 1000)
    turns = [t.request(2, k, 4) for k in range(6)]
    p = turns[1]["shared"]
    assert turns[0]["shared"] == 0 and p >= 20
    assert turns[0]["prompt"][:p] == turns[1]["prompt"][:p] \
        == turns[2]["prompt"][:p]
    assert turns[3]["prompt"][:20] != turns[0]["prompt"][:20]
    assert turns[1]["think_s"] == 0.25 and turns[3]["think_s"] == 0.0
    assert t.max_total() >= max(len(r["prompt"]) + r["max_new_tokens"]
                                for r in turns)


def test_gamma_is_burstier_than_poisson():
    import numpy as np
    base = spec(LAWS[0], KINDS[0], None)
    cv = {}
    for arr in ARRIVALS[1:]:
        due = gen.Traffic(dict(base, arrival=dict(arr, rate=200.0)),
                          1, 10).arrivals(20.0)
        gaps = np.diff(due)
        cv[arr["kind"]] = gaps.std() / gaps.mean()
    assert 0.9 < cv["poisson"] < 1.1 and 1.8 < cv["gamma"] < 2.2


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(GRID, "traffic"))))
def test_every_traffic_file_loads(name):
    """A traffic mix is data: the files that are there, the mixes that
    no cell runs yet among them, all load in the one generator."""
    with open(os.path.join(GRID, "traffic", name + ".json")) as f:
        s = json.load(f)
    assert len(s["why"]) <= 200
    if "arrival" not in s:
        assert s["job"] == "pretrain" and s["seq_len"] > 0
        return
    t = gen.Traffic(s, 9, 32768)
    r = t.request(0, 0, 2)
    assert r["max_new_tokens"] >= 1 and r["prompt"]
    assert t.prefill_lengths() and t.check_batch(4, 512, 16)
    assert len(t.warmup([128, 256])) >= 2
