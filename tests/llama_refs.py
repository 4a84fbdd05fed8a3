"""Shared llama serve-test scaffolding (the tier-1 test-budget seam).

Every serve-tier test file used to build its own tiny-llama config,
init its own weight trees, and recompute ``llama.generate`` reference
streams per test — on CPU those references are the dominant cost of
timed tier-1. This module interns all three ONCE per session:

- :func:`serve_config` / :func:`serve_weights`: the standard tiny
  float32 config and per-seed weight trees, shared across files (one
  tree per seed → reference memoization actually hits across files);
- :func:`reference`: memoized ``llama.generate`` — keyed on the
  weight tree identity + the full sampling config, so the same
  (prompt, mnew, seed) asked by test_serve, test_gateway and
  test_fleet compiles and runs generate once;
- :func:`engine_factory`: the standard tier-1 engine shape
  (max_slots=2, max_len=32, min_bucket=4). Serve tests MUST reuse
  this shape — XLA's CPU JIT sits near process-wide code capacity in
  tier-1, and every novel (bucket, max_len) pair compiles fresh
  programs (a late compile can segfault the process).
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from mxtpu.models import llama

_CFG = None
_WEIGHTS = {}
_REFS = {}
_PINNED = {}       # id(tree) -> tree: keys stay valid (no id reuse)


def serve_config():
    """The standard serve-test config: tiny llama, float32, dense
    attention, no remat — one instance per session."""
    global _CFG
    if _CFG is None:
        _CFG = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                       remat=False, attn_impl="dense")
    return _CFG


def serve_weights(seed: int = 0):
    """Session-interned weight tree for ``PRNGKey(seed)`` (seed 0 is
    'params', seed 1 is the second model of two-model tests)."""
    tree = _WEIGHTS.get(seed)
    if tree is None:
        tree = _WEIGHTS[seed] = llama.init_params(
            serve_config(), jax.random.PRNGKey(seed))
    return tree


def reference(cfg, params, prompt, mnew, *, seed=0, temperature=0.0,
              top_k=None, top_p=None):
    """Memoized batch-1 ``llama.generate`` oracle: the exact token
    list the serving stack must reproduce. Keyed on the weight-tree
    identity (the tree is pinned so the id can never be recycled) and
    every knob that changes the stream."""
    prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
    key = (id(params), tuple(prompt), int(mnew), int(seed),
           float(temperature), top_k, top_p)
    toks = _REFS.get(key)
    if toks is None:
        out = llama.generate(
            cfg, params, jnp.asarray(prompt, jnp.int32)[None], mnew,
            temperature=temperature, top_k=top_k, top_p=top_p,
            rng=jax.random.PRNGKey(seed))
        toks = _REFS[key] = [int(t) for t in
                             np.asarray(out)[0, len(prompt):]]
        _PINNED[id(params)] = params
    return list(toks)


def engine_factory(cfg, params, **kw):
    """Zero-arg factory for the STANDARD tier-1 engine shape; accepts
    ``params=`` so fleet hot-swap/canary can reload weights into it.
    Extra kwargs override the shape (only do that in slow-marked
    tests — see the module docstring)."""
    from mxtpu.serve import ServeEngine
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("min_bucket", 4)
    return lambda params=params: ServeEngine(cfg, params, **kw)


def mass_before(slg, top_k):
    """float64 ``numpy``, the sampler tests' nucleus oracle: for every
    token of every row, the probability mass of the top-k survivors
    whose value lies strictly above the token's (what the nucleus
    compares with ``top_p``); a tie-class shares the mass above its
    first member. slg: (b, V) the logits over the temperature; top_k:
    (b,)."""
    slg = np.asarray(slg, np.float64)
    out = np.empty_like(slg)
    for i, row in enumerate(slg):
        order = np.argsort(-row, kind="stable")
        srt = row[order]
        srt = np.where(srt < srt[int(top_k[i]) - 1], -np.inf, srt)
        probs = np.exp(srt - srt[0])
        probs /= probs.sum()
        before = np.cumsum(probs) - probs
        out[i, order] = before[np.searchsorted(-srt, -srt, side="left")]
    return out
