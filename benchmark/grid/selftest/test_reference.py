"""The plain reference against the program at a tiny size: the
near-argmax check passes on what the engine emits and fails on a
shifted position; the reference's loss and gradients are the
program's."""
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from program import llama_config, seed_key
from reference import decoder

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def tiny():
    from mxtpu.models import llama
    with open(os.path.join(HERE, "configs", "tiny-serve.json")) as f:
        config = json.load(f)
    cfg = llama_config(config, config["run"])
    params = jax.jit(partial(llama.init_params, cfg))(seed_key(2 ** 31 + 7))
    return config, cfg, params


@pytest.fixture(scope="module")
def emitted(tiny):
    """What the paged engine emits, greedily, for three prompts."""
    from mxtpu.serve import Request, ServeEngine
    config, cfg, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, config["vocab_size"], n).tolist()
               for n in (37, 130, 200)]
    engine = ServeEngine(cfg, params, paged=True, max_slots=4,
                         max_len=512, min_bucket=128, page_size=16,
                         prefix_cache=True)
    rids = [engine.submit(Request(np.asarray(p, np.int32), 12))
            for p in prompts]
    out = engine.run()
    return prompts, [out[r].tolist() for r in rids]


def test_emitted_tokens_are_the_references_argmax(tiny, emitted):
    config, _, params = tiny
    for prompt, toks in zip(*emitted):
        gaps = np.asarray(decoder.argmax_gaps(config, params, prompt,
                                              toks, 256))
        assert gaps.shape == (12,) and gaps.max() <= 1e-4, gaps


def test_a_shifted_position_fails_the_check(tiny, emitted):
    """The same tokens held against a context that is one position
    off (every token read one place late, the last one of the context
    wrong) — what a wrong page, offset or mask would produce — lie far
    below the maximum: with random weights a wrong token is a draw
    from the whole vocabulary."""
    config, _, params = tiny
    for prompt, toks in zip(*emitted):
        gaps = np.asarray(decoder.argmax_gaps(
            config, params, prompt + prompt[:1], toks, 256))
        assert gaps.max() > 0.5, gaps
        # and so do the right tokens in the wrong order
        swapped = toks[1:] + toks[:1]
        gaps = np.asarray(decoder.argmax_gaps(config, params, prompt,
                                              swapped, 256))
        assert gaps.max() > 0.5, gaps


def test_loss_and_gradients_are_the_programs(tiny):
    from mxtpu.models import llama
    config, cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, config["vocab_size"], (2, 64)), jnp.int32)
    sys_fn = llama.loss_fn(cfg)
    want, gwant = jax.value_and_grad(
        lambda p: decoder.loss(config, p, tokens))(params)
    got, ggot = jax.value_and_grad(
        lambda p: sys_fn(p, {"tokens": tokens}))(params)
    assert abs(float(got) - float(want)) < 1e-4
    flat_w = jax.tree.leaves(gwant)
    flat_g = jax.tree.leaves(ggot)
    assert len(flat_w) == len(flat_g)
    for a, b in zip(flat_g, flat_w):
        scale = float(jnp.abs(b).max()) + 1e-8
        assert float(jnp.abs(a - b).max()) / scale < 2e-3
