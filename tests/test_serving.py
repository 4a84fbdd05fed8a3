"""The seam between the serving families and ``models/serving.py``.

Contracts:
- every chunked family's three admission programs are module-level
  functions of its own module, under the names and with the parameters
  the engine compiles them by (``ServeEngine`` names a program by the
  function's ``__name__``; the benchmark's readers look for
  ``jit_prefill_slot_paged_last_b1024``) — forwards to the skeleton,
  not ``functools.partial`` objects;
- no family module writes the skeleton again: no first-token seat, page
  seat, sampler or small helper of its own, and llama keeps none of the
  pieces that moved;
- the skeleton's pieces do what the families relied on: the page seat
  writes a stage's rows page by page and drops rows past the slot's
  pages, the slot seat sets one slot's vectors, the decode step's end
  puts the routing counts behind the tokens.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import models
from mxtpu.models import llama, serving

CHUNKED = sorted(name for name, fam in models.SERVING_FAMILIES.items()
                 if hasattr(fam, "prefill_slot_paged_chunk"))
PROGRAMS = {
    "prefill_slot_paged": list(inspect.signature(
        llama.prefill_slot_paged).parameters),
    "prefill_slot_paged_chunk": ["cfg", "params", "tokens", "start",
                                 "stage", "mesh"],
    "prefill_slot_paged_last": ["cfg", "params", "tokens", "start",
                                "n_valid", "stage", "pages_row", "slot",
                                "kv", "sv", "rng", "temperature", "top_k",
                                "top_p", "mesh"],
}


def test_every_family_but_llama_chunks():
    assert CHUNKED == ["blockdiff_moe", "latent_moe", "nemotron_h",
                       "retention", "sambay"]


@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("family", CHUNKED)
def test_admission_programs_are_the_family_functions_the_engine_names(
        family, program):
    fam = models.SERVING_FAMILIES[family]
    fn = getattr(fam, program)
    assert inspect.isfunction(fn)
    assert fn.__name__ == program and fn.__module__ == fam.__name__
    params = inspect.signature(fn).parameters
    assert list(params) == PROGRAMS[program]
    assert params["mesh"].default is None
    # a forward: the family's two parts handed to the skeleton (a whole
    # prompt may be the last chunk of itself from a fresh stage)
    skeleton = {"prefill_slot_paged": ("prefill_whole", "prefill_last"),
                "prefill_slot_paged_chunk": ("prefill_chunk",),
                "prefill_slot_paged_last": ("prefill_last",)}[program]
    assert any(f"serving.{name}(" in inspect.getsource(fn)
               for name in skeleton)


SKELETON = ("_seat_first", "_seat_rows", "_seat_state", "_kv_block",
            "_normal", "_neg_exp", "_sample_slots", "_masked_logits",
            "_draw", "_write_pages")


@pytest.mark.parametrize("family", CHUNKED + ["llama"])
def test_a_family_writes_no_skeleton_of_its_own(family):
    fam = models.SERVING_FAMILIES[family]
    own = {n for n, v in vars(fam).items()
           if inspect.isfunction(v) and v.__module__ == fam.__name__}
    assert not own & set(SKELETON)
    # llama's sampler is the skeleton's, under its old name
    assert llama.sample_logits is serving.sample_logits


def test_seat_pages_writes_a_stage_page_by_page():
    """Rows (L, 1, capacity, width) land in the slot's pages in order; a
    stage longer than the slot's pages is cut at them."""
    L, ps, width, n_pages = 2, 4, 3, 6
    pool = jnp.zeros((L, n_pages, ps, width), jnp.float32)
    rows = jnp.arange(L * 12 * width, dtype=jnp.float32).reshape(
        L, 1, 12, width)
    pages_row = jnp.asarray([5, 2], jnp.int32)      # 8 positions held
    got = np.asarray(serving.seat_pages(pool, rows, pages_row))
    want = np.zeros(got.shape, np.float32)
    want[:, 5] = np.asarray(rows)[:, 0, 0:4]
    want[:, 2] = np.asarray(rows)[:, 0, 4:8]
    np.testing.assert_array_equal(got, want)


def test_seat_slot_sets_one_slot_and_leaves_the_rest():
    sv = serving.slot_vectors(3)
    rng = jnp.asarray([7, 9], jnp.uint32)
    got = serving.seat_slot(sv, jnp.int32(1), jnp.int32(11),
                            jnp.asarray([42], jnp.int32), rng)
    np.testing.assert_array_equal(got["lengths"], [0, 11, 0])
    np.testing.assert_array_equal(got["tokens"], [0, 42, 0])
    np.testing.assert_array_equal(got["rngs"], [[0, 0], [7, 9], [0, 0]])


def test_decode_step_puts_the_counts_behind_the_tokens():
    """Greedy rows take their argmax; the lengths of the active slots
    advance; assignments, experts touched and the busiest share (in
    millionths) follow the tokens."""
    S, V = 3, 5
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((S, V)),
                         jnp.float32)
    sv = dict(serving.slot_vectors(S), lengths=jnp.asarray([4, 0, 2]))
    sv["rngs"] = jax.vmap(jax.random.PRNGKey)(jnp.arange(S))
    kv = {"pool": jnp.ones((2,))}
    zeros = jnp.zeros((S,), jnp.float32)
    out, kv_out, new = serving.decode_step(
        logits, kv, sv, jnp.asarray([True, False, True]), zeros,
        jnp.full((S,), V, jnp.int32), jnp.ones((S,), jnp.float32),
        counts=jnp.asarray([12, 7, 3], jnp.int32))
    assert kv_out is kv
    np.testing.assert_array_equal(out[:S], np.argmax(logits, -1))
    np.testing.assert_array_equal(out[S:], [12, 7, 250000])
    np.testing.assert_array_equal(new["lengths"], [5, 0, 3])
    np.testing.assert_array_equal(new["tokens"], out[:S])
