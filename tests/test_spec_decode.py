"""Speculative decoding with a bit-exact verify oracle (ISSUE 19).

Contracts:
- the accept oracle is IDENTITY against the target chain (Leviathan et
  al. 2023 greedy case, extended to sampling by drafting ahead of the
  same rng chain): a speculative engine streams bit-identical to
  per-request ``llama.generate`` for greedy AND sampled configs, no
  matter what the drafter proposes — an adversarial drafter can only
  cost speed, never tokens;
- the rng contract survives multi-token emission: exactly one
  ``jax.random.split`` is consumed per VALID emission, so
  ``resume_key(seed, n_emitted)`` re-seats a crashed request
  mid-accepted-run (the journaled paged resume path replays the
  accepted-count advance);
- :func:`ngram_drafter` is pure host arithmetic: longest trailing
  n-gram (g = 3, 2, 1) at its most recent earlier occurrence, extended
  periodically so a plateau drafts the full budget;
- the compile bound is the paged baseline + ONE program: prefill
  buckets + decode + copy_page + spec verify, however the per-step
  accept lengths vary.

The fresh-process home for the end-to-end gate is the ``spec_smoke``
CI stage (ci_fast + ci_all); the heavier matrix here is slow-marked.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu.models import llama
from mxtpu.serve import Request, ServeEngine, resume_key
from mxtpu.serve.engine import KVHandoff, ngram_drafter

import llama_refs


@pytest.fixture(scope="module")
def cfg(serve_cfg):
    return serve_cfg


@pytest.fixture(scope="module")
def params(serve_params):
    return serve_params


def spec_engine(cfg, params, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("speculate_k", 3)
    return llama_refs.engine_factory(cfg, params, **kw)()


# ---------------------------------------------------------------------------
# drafter: pure host n-gram lookup with periodic extension
# ---------------------------------------------------------------------------
def test_ngram_drafter_plateau_drafts_full_budget():
    # period-1 stream: a single repeated token must fill the whole
    # budget (the pre-extension drafter proposed ONE token here, which
    # capped the speedup at 2x no matter how long the plateau ran)
    out = ngram_drafter(np.asarray([9, 142, 142, 142, 142]), 4)
    assert out.tolist() == [142, 142, 142, 142]
    assert out.dtype == np.int32


def test_ngram_drafter_periodic_extension_cycles():
    # trailing gram [1, 2] last seen 2 back -> period 2, draft cycles
    out = ngram_drafter(np.asarray([7, 1, 2, 1, 2]), 5)
    assert out.tolist() == [1, 2, 1, 2, 1]


def test_ngram_drafter_prefers_longest_gram():
    # g=3 history match [5, 6, 7] -> 8 beats the g=1 match of the
    # trailing 7 alone (which would draft its other successor, 9)
    h = np.asarray([5, 6, 7, 8, 0, 7, 9, 5, 6, 7])
    out = ngram_drafter(h, 1)
    assert out.tolist() == [8]


def test_ngram_drafter_most_recent_occurrence_wins():
    # the SAME gram occurs twice with different successors: the more
    # recent occurrence (closer to the stream's current regime) wins
    h = np.asarray([3, 4, 3, 5, 3])
    out = ngram_drafter(h, 1)
    assert out.tolist() == [5]


def test_ngram_drafter_degenerate_inputs_draft_nothing():
    assert ngram_drafter(np.asarray([1, 2, 3, 4]), 3).size == 0  # novel
    assert ngram_drafter(np.asarray([7]), 3).size == 0           # n < 2
    assert ngram_drafter(np.asarray([7, 7, 7]), 0).size == 0     # k < 1
    assert ngram_drafter(np.empty(0, np.int32), 3).size == 0


def test_speculate_k_constructor_validation(cfg, params):
    with pytest.raises(ValueError):
        llama_refs.engine_factory(cfg, params, page_size=8,
                                  speculate_k=-1)()
    # nothing but the count: the verify program runs over the one bank
    e = llama_refs.engine_factory(cfg, params, speculate_k=2)()
    assert e.speculate_k == 2 and e.overlap is False
    assert e.kv_cache_stats()["speculate_k"] == 2


# ---------------------------------------------------------------------------
# engine: bit-identity is unconditional; speed is the only variable
# ---------------------------------------------------------------------------
@pytest.mark.slow   # ~14s; fresh-process home: spec_smoke (ci_fast)
def test_spec_engine_bit_identical_mixed_configs(cfg, params):
    # [140, 141, 140] plateaus greedily within a couple of tokens on
    # the tiny weights, so the default drafter actually fires; the
    # sampled rows exercise the rng-chain half of the oracle
    reqs = [
        dict(prompt=[140, 141, 140], max_new_tokens=12,
             temperature=0.0),
        dict(prompt=[140, 141, 140], max_new_tokens=10,
             temperature=0.0, seed=1),
        dict(prompt=[9, 4, 7, 1, 6], max_new_tokens=6,
             temperature=1.0, seed=2),
        dict(prompt=[21, 22, 23], max_new_tokens=5, temperature=0.9,
             top_k=7, seed=3),
    ]
    e = spec_engine(cfg, params)
    rids = [e.submit(Request(**r)) for r in reqs]
    out = e.run()
    for rid, r in zip(rids, reqs):
        want = llama_refs.reference(
            cfg, params, r["prompt"], r["max_new_tokens"],
            seed=r.get("seed", 0), temperature=r["temperature"],
            top_k=r.get("top_k"))
        assert [int(t) for t in out[rid]] == want, r
    st = e.kv_cache_stats()
    assert st["spec_steps"] > 0, st         # speculation actually ran
    assert st["spec_accepted"] > 0, st      # the plateau was accepted
    assert 0.0 <= st["spec_accept_rate"] <= 1.0, st
    # variable accept lengths never retrace: baseline + ONE program
    assert e.compile_count <= e.n_buckets + 3, (e.compile_count,
                                                e.n_buckets)


@pytest.mark.slow   # ~8s; adversarial-drafter half of the oracle gate
def test_adversarial_drafter_never_changes_tokens(cfg, params):
    """A drafter proposing garbage costs verify compute only: every
    wrong draft is rejected by the identity oracle and the stream is
    STILL bit-identical — the correctness/performance split that makes
    the drafter pluggable without a proof obligation."""
    wrong = spec_engine(cfg, params, drafter=lambda h, k: np.full(
        k, 3, np.int32))                   # constant garbage
    silent = spec_engine(cfg, params, drafter=lambda h, k: np.empty(
        0, np.int32))                      # never drafts: plain path
    p, mnew = [17, 3, 9], 8
    want = llama_refs.reference(cfg, params, p, mnew, seed=4,
                                temperature=0.9, top_k=5)
    for e in (wrong, silent):
        rid = e.submit(Request(prompt=p, max_new_tokens=mnew,
                               temperature=0.9, top_k=5, seed=4))
        assert [int(t) for t in e.run()[rid]] == want
    # the silent drafter never built a speculative step at all
    assert silent.kv_cache_stats()["spec_steps"] == 0
    assert wrong.kv_cache_stats()["spec_steps"] > 0


@pytest.mark.slow   # ~10s; the accepted-count rng-advance gate
def test_spec_sampled_full_acceptance_multi_token_steps(cfg, params):
    """Force multi-token emission on a SAMPLED stream (an oracle
    drafter that reads the reference) — the engine must fast-forward
    the rng chain by the ACCEPTED count, not by steps: fewer steps
    than tokens, same tokens."""
    p, mnew, seed = [9, 4, 7, 1], 8, 5
    ref = llama_refs.reference(cfg, params, p, mnew, seed=seed,
                               temperature=0.9, top_k=7)

    def oracle(hist, k):
        n_em = int(hist.size) - len(p)     # hist = prompt + emitted
        if not 0 <= n_em < mnew:
            return np.empty(0, np.int32)
        return np.asarray(ref[n_em:n_em + k], np.int32)

    e = spec_engine(cfg, params, drafter=oracle)
    rid = e.submit(Request(prompt=p, max_new_tokens=mnew,
                           temperature=0.9, top_k=7, seed=seed))
    assert [int(t) for t in e.run()[rid]] == ref
    st = e.kv_cache_stats()
    assert st["spec_accepted"] >= mnew // 2, st
    assert e.steps_run < mnew, (e.steps_run, mnew)   # multi-advance


@pytest.mark.slow   # ~12s; journaled paged resume through spec engines
def test_spec_journaled_resume_replays_accepted_rng(cfg, params):
    """Crash re-dispatch across SPECULATIVE engines: the first engine
    emits its prefix via multi-token accepted runs, then a fresh spec
    engine seats the journaled handoff with ``resume_key(seed,
    n_emitted)`` — n_emitted counts EMISSIONS (the chain advanced once
    per valid token), so the resumed stream continues bit-exactly even
    though the crash point fell mid-accepted-run."""
    p, mnew, seed = [9, 4, 7, 1], 8, 5
    ref = llama_refs.reference(cfg, params, p, mnew, seed=seed,
                               temperature=0.9, top_k=7)

    def oracle(hist, k):
        n_em = int(hist.size) - len(p)
        if not 0 <= n_em < mnew:
            return np.empty(0, np.int32)
        return np.asarray(ref[n_em:n_em + k], np.int32)

    # run 1: spec engine, multi-token steps (proves the prefix came
    # from accepted runs, not plain stepping)
    e1 = spec_engine(cfg, params, drafter=oracle)
    r1 = e1.submit(Request(prompt=p, max_new_tokens=mnew,
                           temperature=0.9, top_k=7, seed=seed))
    assert [int(t) for t in e1.run()[r1]] == ref
    assert e1.steps_run < mnew

    # crash after 5 emitted (inside an accepted run of e1's stepping):
    # journaled handoff carries the PROMPT block + post-prefill chain
    padded = np.zeros((1, 4), np.int32)    # bucket 4 covers len 4
    padded[0, :len(p)] = p
    tok, kb, vb, rng = llama.prefill_detached(
        cfg, params, jnp.asarray(padded), np.int32(len(p)),
        jax.random.PRNGKey(seed), np.float32(0.9), np.int32(7),
        np.float32(1.0))
    assert int(np.asarray(tok)[0]) == ref[0]
    h = KVHandoff(k=np.asarray(kb), v=np.asarray(vb), true_len=len(p),
                  token=ref[0], rng=np.asarray(rng, np.uint32))
    n_em = 5
    e2 = spec_engine(cfg, params, drafter=oracle)
    rid = e2.submit_prefilled(h, Request(
        prompt=p + ref[:n_em], max_new_tokens=mnew - n_em,
        temperature=0.9, top_k=7, rng=resume_key(seed, n_em)))
    assert [int(t) for t in e2.run()[rid]] == ref[n_em:]


@pytest.mark.slow   # ~9s; spec over SHARED CoW pages stays bit-exact
def test_spec_over_shared_prefix_pages(cfg, params):
    """Speculative accepted runs write through the page-table
    indirection into FORKED boundary pages — sharing must change no
    tokens (the prefix-affinity routing story depends on it)."""
    shared = [7, 3, 9, 1, 5, 2, 8, 4, 6]   # 9 toks > page_size 8
    e = spec_engine(cfg, params)
    # cold wave registers the prompt; the warm wave (a SECOND run, so
    # registration has landed) shares its full page + forks the
    # boundary page, then speculates into the fork
    reqs = [dict(prompt=shared + [11], max_new_tokens=6,
                 temperature=0.0),
            dict(prompt=shared + [12], max_new_tokens=6,
                 temperature=1.0, seed=1)]
    for r in reqs:
        rid = e.submit(Request(**r))
        assert [int(t) for t in e.run()[rid]] == llama_refs.reference(
            cfg, params, r["prompt"], r["max_new_tokens"],
            seed=r.get("seed", 0), temperature=r["temperature"])
    st = e.kv_cache_stats()
    assert st["prefix_hits"] >= 1 and st["cow_forks"] >= 1, st
