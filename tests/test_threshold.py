"""The sampler's two thresholds by search (``mxtpu.ops.threshold``)
against the sort they replaced, which lives on HERE as the reference.

Contracts:
- ``thresholds`` masks a row as the sorted form did: the same ``kth``
  value, and a cut-off that keeps the smallest prefix of the sorted
  distribution whose mass reaches ``p``, the top token always, a
  tie-class whole. Only the order in which float32 masses are added
  differs, so a kept set may differ from the sort's by tokens whose mass
  lies within float32's rounding of ``top_p`` (held in float64);
- the Pallas kernel, interpreted, equals the ``jnp`` form bit for bit;
- ``_sample_slots`` (the decode step's bank at once) draws the tokens
  the slot-by-slot form drew on the same keys;
- ``thresholds_path`` picks the form from backend, rows, dtype and mesh,
  and a block of fewer than eight rows never traces the kernel.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import llama_refs
from mxtpu.models import llama, serving
from mxtpu.ops import threshold

V = 1031                # no multiple of 128: the kernel pads with -inf
KINDS = ["normal_t0.6", "normal_t0.7", "normal_t1.0", "ties",
         "signed_zeros", "inf_padding"]


def _rows(kind, rows=4, vocab=V):
    """(rows, vocab) float32 logits, already over their temperature."""
    rng = np.random.default_rng(len(kind) + rows)
    x = rng.standard_normal((rows, vocab)).astype(np.float32) * 2
    if kind.startswith("normal_t"):
        return x / np.float32(kind[len("normal_t"):])
    if kind == "ties":          # ~20 distinct values: the kth value and
        return np.round(x * 2) / 2      # the cut-off fall inside a tie
    if kind == "signed_zeros":  # whole numbers, +0.0 and -0.0 among them
        x = np.round(x / 2)
        zeros = x == 0
        assert np.signbit(x[zeros]).any() and not np.signbit(x[zeros]).all()
        return x
    assert kind == "inf_padding"
    x[rng.random(x.shape) < 0.3] = -np.inf
    x[-1, 7:] = -np.inf         # seven candidates left
    return x


def _sorted_thresholds(lg, k, p):
    """The reference: both thresholds read off ONE sort of the values,
    as ``models/llama.py`` found them from PR 28 to PR 33
    (``_sort_descending``, ``_nucleus_cutoff``). lg (rows, V) float32; k
    (rows, 1) ints; p (rows, 1). Returns (kth, cutoff), (rows, 1)."""
    srt = -lax.sort(-lg, dimension=-1, is_stable=False)
    kth = jnp.take_along_axis(srt, k - 1, axis=-1)
    srt = jnp.where(srt < kth, -jnp.inf, srt)
    probs = jax.nn.softmax(srt, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < p
    return kth, jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                        keepdims=True)


def _cols(rows, k, p):
    k = np.broadcast_to(np.asarray(k, np.int32).reshape(-1, 1), (rows, 1))
    p = np.broadcast_to(np.asarray(p, np.float32).reshape(-1, 1), (rows, 1))
    return jnp.asarray(k), jnp.asarray(p)


def _assert_masks_as_sorted(x, k, p, kth, cut):
    """(kth, cut) keep what the sorted reference keeps, but for tokens
    whose float64 mass-before lies within 1e-6 of ``top_p``."""
    vocab = x.shape[-1]
    want_kth, want_cut = (np.asarray(a) for a in
                          _sorted_thresholds(jnp.asarray(x), k, p))
    kth, cut, k, p = (np.asarray(a) for a in (kth, cut, k, p))
    # -inf is "no threshold": the sort's kth at k = V is the row's minimum
    np.testing.assert_array_equal(np.where(k < vocab, kth, want_kth),
                                  want_kth)
    assert (kth[k >= vocab] == -np.inf).all()
    assert (cut[p >= 1.0] == -np.inf).all()
    kept = x >= np.maximum(kth, cut)
    want = x >= np.maximum(want_kth, want_cut)
    differ = kept != want
    if differ.any():
        before = llama_refs.mass_before(x, k[:, 0])
        off = differ & (np.abs(before - p.astype(np.float64)) > 1e-6)
        assert not off.any(), np.argwhere(off)[:8]
    # the top token always survives; a tie-class is kept or cut whole
    assert kept[np.arange(len(x)), x.argmax(-1)].all()
    for row, keep in zip(x, kept):
        assert not (set(row[keep].tolist()) & set(row[~keep].tolist()))


@pytest.mark.parametrize("top_p", [0.1, 0.95, 1.0])
@pytest.mark.parametrize("top_k", [1, 40, "V"])
@pytest.mark.parametrize("kind", KINDS)
def test_thresholds_mask_as_the_sort_did(kind, top_k, top_p):
    x = _rows(kind)
    k, p = _cols(len(x), V if top_k == "V" else top_k, top_p)
    kth, cut = jax.jit(threshold.thresholds)(jnp.asarray(x), k, p)
    _assert_masks_as_sorted(x, k, p, kth, cut)


@pytest.mark.parametrize("form", ["jnp_against_sort",
                                  "kernel_against_jnp"])
@pytest.mark.parametrize("rows", [1, 8, 9, 32])
def test_thresholds_by_rows(rows, form):
    """One row (a prefill sample), a whole block, a block and a row, a
    decode bank: every row asks for something else, every fourth for
    nothing. The kernel, interpreted, pads 1 and 9 rows to whole blocks
    of eight and equals the ``jnp`` form bit for bit."""
    vocab = 640             # five tiles: no whole group of the kernel's
    x = _rows("normal_t0.7", rows, vocab)
    x[::3] = np.round(x[::3])                   # ties, signed zeros
    i = np.arange(rows)
    k, p = _cols(rows, np.where(i % 4 == 3, vocab, 1 + 13 * (i % 5)),
                 np.where(i % 4 == 3, 1.0, [0.95, 0.1, 0.5][rows % 3]))
    got = jax.jit(threshold.thresholds)(jnp.asarray(x), k, p)
    if form == "jnp_against_sort":
        _assert_masks_as_sorted(x, k, p, *got)
        return
    kernel = jax.jit(partial(threshold.thresholds, interpret=True))(
        jnp.asarray(x), k, p)
    for a, b in zip(kernel, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_block_no_row_asks_of_runs_no_search():
    """``top_k`` of the vocabulary's size and ``top_p`` of 1 on every
    row (the check batch's greedy rows): both thresholds ``-inf``."""
    x = jnp.asarray(_rows("normal_t1.0"))
    k, p = _cols(4, V, 1.0)
    for got in (threshold.thresholds(x, k, p),
                threshold.thresholds(x, k, p, interpret=True)):
        for a in got:
            assert a.shape == (4, 1) and (np.asarray(a) == -np.inf).all()


@pytest.mark.parametrize("kind", ["normal_t0.6", "ties"])
def test_a_greedy_row_among_sampling_rows(kind):
    """A temperature-0 row asks for no threshold and takes its argmax,
    whatever its ``top_k`` and ``top_p`` say; its neighbours' thresholds
    are what they are without it."""
    x = jnp.asarray(_rows(kind))
    t = jnp.asarray([0.7, 0.0, 0.9, 0.0], jnp.float32)
    k = jnp.asarray([40, 1, V, 5], jnp.int32)
    p = jnp.asarray([0.95, 0.1, 0.5, 0.9], jnp.float32)
    greedy, masked = serving._masked_logits(x, t, k, p)
    np.testing.assert_array_equal(
        np.asarray(greedy), [-1, int(x[1].argmax()), -1, int(x[3].argmax())])
    np.testing.assert_array_equal(np.asarray(masked[1]), np.asarray(x[1]))
    _, alone_masked = serving._masked_logits(x[::2], t[::2], k[::2],
                                             p[::2])
    np.testing.assert_array_equal(np.asarray(masked[::2]),
                                  np.asarray(alone_masked))
    toks = llama.sample_logits(jax.random.PRNGKey(3), x, temperature=t,
                               top_k=k, top_p=p)
    assert int(toks[1]) == int(x[1].argmax())
    assert int(toks[3]) == int(x[3].argmax())


def _sample_slot(key, lg, temperature, top_k, top_p):
    """The slot-by-slot form the decode programs ran under ``vmap`` up
    to PR 33: split the slot's chain, sample on (1, V)."""
    key, sub = jax.random.split(key)
    tok = llama.sample_logits(sub, lg[None], temperature=temperature,
                              top_k=top_k, top_p=top_p)[0]
    return key, tok


@pytest.mark.parametrize("slots", ["all_sample", "some_greedy",
                                   "every_config"])
def test_sample_slots_draws_what_slot_by_slot_drew(slots):
    S = 9
    x = jnp.asarray(_rows("normal_t1.0", S, 257)) * 2
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(S) + 17)
    i = np.arange(S)
    t, k, p = {
        "all_sample": (np.full(S, 0.7), np.full(S, 257), np.full(S, 0.95)),
        "some_greedy": (np.where(i % 3 == 0, 0.0, 0.6), np.full(S, 257),
                        np.full(S, 0.95)),
        "every_config": (np.where(i == 4, 0.0, 0.5 + i / 10),
                         np.where(i % 2, 257, 1 + 3 * i),
                         np.where(i % 3, 0.9, 1.0))}[slots]
    args = (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))
    want_keys, want = jax.vmap(_sample_slot)(keys, x, *args)
    got_keys, got = jax.jit(serving._sample_slots)(keys, x, *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_keys),
                                  np.asarray(want_keys))


class _AMesh:
    """``thresholds_path`` only asks whether there is one."""


@pytest.mark.parametrize("backend,shape,dtype,mesh,path", [
    ("cpu", (32, 32768), jnp.float32, None, "search"),
    ("tpu", (32, 32768), jnp.float32, None, "search_kernel"),
    ("tpu", (16, 151936), jnp.float32, None, "search_kernel"),
    ("tpu", (32, 200064), jnp.float32, None, "search_kernel"),
    ("tpu", (8, 1031), jnp.float32, None, "search_kernel"),
    # fewer than eight rows: a prefill program's sample, generate at 1
    ("tpu", (7, 32768), jnp.float32, None, "search"),
    ("tpu", (1, 32768), jnp.float32, None, "search"),
    ("tpu", (1, 200064), jnp.float32, None, "search"),
    ("tpu", (32, 32768), jnp.bfloat16, None, "search"),
    ("tpu", (32, 32768), jnp.float32, _AMesh(), "search"),
    ("tpu", (4, 8, 32768), jnp.float32, None, "search"),
    # three copies of the block past the VMEM the kernel may ask for
    ("tpu", (32, 2 ** 20), jnp.float32, None, "search"),
], ids=lambda v: getattr(v, "__name__", None) or (
    "mesh" if isinstance(v, _AMesh) else str(v)))
def test_thresholds_path(monkeypatch, backend, shape, dtype, mesh, path):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert threshold.thresholds_path(shape, dtype, mesh=mesh) == path


@pytest.mark.parametrize("rows,kernels", [(1, 0), (7, 0), (8, 1), (32, 1)])
def test_the_kernel_is_traced_for_eight_rows_or_more(monkeypatch, rows,
                                                     kernels):
    """What a TPU's program holds: a one-row sample (every prefill
    program's) traces no ``pallas_call``, a bank of eight or more ONE."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    k, p = _cols(rows, 40, 0.95)
    jaxpr = jax.make_jaxpr(threshold.thresholds)(
        jnp.zeros((rows, V), jnp.float32), k, p)
    names = [e.params["name"] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert names == [threshold.KERNEL_NAME] * kernels
