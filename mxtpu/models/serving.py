"""The serving skeleton every family shares, and the contract between a
family module and ``serve.ServeEngine``, which takes every program it
runs from one module (``models.serving_family(cfg)``).

What the engine reads of a family module: ``init_params``;
``init_paged_cache(cfg, max_slots, n_pages, page_size, mesh=None,
int8=False)``, the donated state by name plus the slot vectors;
``decode_attention_path(cfg, kv, mesh=None, *, verify=False)``;
``decode_slots_paged`` (``params, kv, sv, active, page_table,
temperature, top_k, top_p, mesh`` -> (tokens, kv, sv)) or, where a step
yields nothing or a block a slot, ``block_step_slots_paged``;
``copy_page``; ``prefill_slot_paged`` (a whole prompt); for a prompt a
chunk at a time ``init_prefill_stage(cfg, capacity, chunk)``, which
refuses a chunk its programs cannot take, ``prefill_slot_paged_chunk``
and ``prefill_slot_paged_last``; and where the family has them
``SERVE_UNSUPPORTED`` (the options refused, with the mechanism in the
way), ``STATE_KINDS`` (the kind of each donated array; one ending in
``_pages`` grows with the tokens held), ``SLOT_VARS`` (:data:`SLOT_VARS`
otherwise), ``STEP_COUNTS`` (what the step counts on the device, behind
its tokens), ``prefill_paths`` and ``serve_gauges``.

What a family writes: its config, weights, layers, ``forward`` and the
hooks its checks read, its kinds of state, ``decode_attention_path``,
``init_prefill_stage`` with its chunk rule, the step up to its logits
(``decode_logits``, ended by :func:`decode_step`), and two parts:

- the run, ``run(cfg, params, tokens, start, n_valid, stage, mesh) ->
  (head, stage)``: its layers over tokens (1, s) at positions ``start
  ..``, the first ``n_valid`` the prompt's (a count known when the
  program is traced is a whole chunk's, not the last), from ``stage`` or
  from a fresh one where it is None (a whole prompt); ``head`` is what
  the end reads besides the stage;
- the end, ``end(cfg, head, state, true_len, pages_row, slot, kv, sv,
  rng, temperature, top_k, top_p)`` -> (first tokens, kv, sv):
  :func:`_seat_first` with the family's state seat, or its own.

What this module builds from them: :func:`prefill_whole`,
:func:`prefill_chunk` and :func:`prefill_last`, to which the family's
``prefill_slot_paged``, ``_chunk`` and ``_last`` hand their arguments in
one statement (the engine names a program by the family function's
``__name__``).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from ..ops.threshold import thresholds

__all__ = ["KV_WRITE_SCOPE", "SAMPLER_SCOPE", "SLOT_VARS", "PAGE_POOLS",
           "slot_vectors", "seat_slot", "sample_logits", "decode_step",
           "busiest_share", "write_pages", "copy_page", "seat_pages",
           "seat_pools", "last_logits", "prefill_whole", "prefill_chunk",
           "prefill_last", "refuse", "normal", "neg_exp", "kv_block"]

# the scopes of the serving programs' shared pieces (``models/llama.py``
# lists the rest of the block's)
KV_WRITE_SCOPE = "kv_write"
SAMPLER_SCOPE = "sampler"

# the per-slot vectors every family keeps beside its donated state:
# ``lengths`` (valid positions), ``tokens`` (the next input token) and
# ``rngs`` (each request's sampling chain)
SLOT_VARS = ("lengths", "tokens", "rngs")
# the names of the token-major page pools (L, n_pages, page_size, ..) a
# family's state may hold: keys and values, their int8 scales, latent
# rows
PAGE_POOLS = ("k", "v", "ks", "vs", "latent")


# ---------------------------------------------------------------------------
# small pieces several families share
# ---------------------------------------------------------------------------
def refuse(family: str, unsupported, mesh, int8: bool):
    """``init_paged_cache``'s refusal of a mesh or of int8 pages, in the
    words of the family's ``SERVE_UNSUPPORTED``."""
    if mesh is not None or int8:
        raise ValueError(f"{family}: " + unsupported[
            "mesh" if mesh is not None else "int8_pages"])


def normal(key, shape, fan_in, dtype):
    """A weight from the seed: normal over the square root of its fan-in."""
    return jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)


def neg_exp(a_log):
    """A state-space layer's ``A = -exp(A_log)``, in float32."""
    return -jnp.exp(a_log.astype(jnp.float32))


def kv_block(s: int) -> int:
    """Keys a step of a prefill attention's loop reads, for a run of s
    queries; a row store's capacity is a multiple of it."""
    return min(512, s)


# ---------------------------------------------------------------------------
# the slot vectors
# ---------------------------------------------------------------------------
def slot_vectors(max_slots: int):
    """Empty :data:`SLOT_VARS` of ``max_slots`` slots."""
    return {"lengths": jnp.zeros((max_slots,), jnp.int32),
            "tokens": jnp.zeros((max_slots,), jnp.int32),
            "rngs": jnp.zeros((max_slots, 2), jnp.uint32)}


def seat_slot(sv, slot, length, token, rng):
    """An admitted request's ``length``, next input ``token`` (1,) and
    chain ``rng`` (2,), after its first token's split, into ``slot``."""
    z = jnp.zeros((), jnp.int32)
    return {
        "lengths": lax.dynamic_update_slice(
            sv["lengths"].astype(jnp.int32), length[None], (slot,)),
        "tokens": lax.dynamic_update_slice(
            sv["tokens"], token.astype(sv["tokens"].dtype), (slot,)),
        "rngs": lax.dynamic_update_slice(
            sv["rngs"], rng[None].astype(sv["rngs"].dtype), (slot, z))}


# ---------------------------------------------------------------------------
# the sampler
# ---------------------------------------------------------------------------
# ``_sample_slots`` and ``_seat_first`` keep the names they had in
# ``llama.py`` and the families: a ``jax.random.split`` lowered first under
# one of them gives the split's instructions in every later program that
# caller's name in their ``op_name``, the paths the benchmark's readers
# take device time by
@jax.named_scope(SAMPLER_SCOPE)
def sample_logits(rng, lg, temperature=0.0, top_k=None, top_p=None,
                  mesh: Optional[Mesh] = None):
    """THE sampler — one shared helper for ``llama.generate`` and the
    continuous-batching serving engine (``mxtpu.serve``). lg: (b, V)
    f32 logits → (b,) int32 tokens.

    Two calling modes, numerically aligned token-for-token:

    - **static** (all of temperature/top_k/top_p are Python numbers or
      None): specializes the jitted graph per config — greedy compiles
      to a bare argmax, top-k uses ``lax.top_k`` — the fast path
      ``generate``'s one-program decode loop wants.
    - **traced** (any of them a jax/numpy array): one graph serves
      every per-row mix — temperature (b,), top_k (b,) ints (vocab
      size disables), top_p (b,) (1.0 disables), with temperature 0
      rows selecting argmax. This is how the serving engine runs
      requests with different sampling configs through ONE compiled
      decode program, with tokens bit-matching the static path: the
      top-k threshold is the same kth VALUE, the nucleus cut-off the
      same search, so the masked logits agree and
      ``jax.random.categorical`` sees identical inputs.

    Nucleus semantics (both modes): keep the smallest prefix of the
    sorted distribution whose mass reaches p (the top token always
    survives, a tie-class is kept or cut as a whole), applied as a
    value threshold rather than a full-vocab scatter.

    No order is built. The sampler needs two numbers a row, the k-th
    largest value and the nucleus cut-off, and both are monotone in the
    threshold: the COUNT of values at or above ``v`` and the MASS of
    values above ``v`` only fall as ``v`` rises. ``ops.threshold``
    finds each by bisection on the float32's ordered bit pattern, 32
    probes of a compare, a select and a sum over the row (on a TPU one
    Pallas kernel with the rows resident in VMEM, for a block of eight
    rows or more; ``jnp`` elsewhere and for fewer),
    exact for any distribution, and skips a search that no row asks for
    (``top_k`` off, ``top_p`` 1, a greedy row). A sort of a 200064-wide
    row was the costliest operation of a decode step (PR 33), and a
    permutation with a ``take_along_axis`` through it a
    vocabulary-sized gather a row besides. ``mesh``: the mesh the
    logits are sharded over, if any (the kernel is not partitioned)."""
    static = (isinstance(temperature, (int, float))
              and (top_k is None or isinstance(top_k, int))
              and (top_p is None or isinstance(top_p, (int, float))))
    V = lg.shape[-1]
    if static:
        if temperature == 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        lg = lg / temperature
        if top_k is not None and top_k < V:
            kth = lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        if top_p is not None and top_p < 1.0:
            lg = _nucleus_mask(lg, top_p, mesh)
        return jax.random.categorical(rng, lg, axis=-1) \
            .astype(jnp.int32)
    greedy, slg = _masked_logits(lg, temperature, top_k, top_p, mesh)
    sampled = jax.random.categorical(rng, slg, axis=-1) \
        .astype(jnp.int32)
    return jnp.where(greedy >= 0, greedy, sampled)


def _masked_logits(lg, temperature, top_k, top_p, mesh=None):
    """The traced mode's rows as ``categorical`` will see them. lg: (b,
    V); temperature, top_k, top_p: None, scalars or (b,). Returns
    (greedy (b,) int32: the argmax of a temperature-0 row, -1 for a row
    that samples; the logits over the temperature, masked to the top-k
    values and, of those, the top-p nucleus)."""
    V = lg.shape[-1]

    def col(x, dtype):          # broadcast a scalar or (b,) over vocab
        x = jnp.asarray(x, dtype)
        x = x.reshape(x.shape + (1,) * (lg.ndim - x.ndim))
        return jnp.broadcast_to(x, lg.shape[:-1] + (1,))

    t_col = col(temperature, jnp.float32)
    samples = t_col != 0.0
    # a greedy row asks for no threshold: its argmax is its token
    k_col = jnp.where(samples, jnp.clip(
        col(V if top_k is None else top_k, jnp.int32), 1, V), V)
    p_col = jnp.where(samples,
                      col(1.0 if top_p is None else top_p, jnp.float32),
                      1.0)
    greedy = jnp.where(jnp.squeeze(samples, -1), -1,
                       jnp.argmax(lg, axis=-1).astype(jnp.int32))
    slg = lg / jnp.where(samples, t_col, 1.0)
    # top-k as a value threshold: the kth-largest VALUE equals
    # lax.top_k's kth element, so the mask matches the static path;
    # the nucleus cut-off is found over the top-k survivors
    kth, cutoff = thresholds(slg, k_col, p_col, mesh=mesh)
    return greedy, jnp.where(slg >= jnp.maximum(kth, cutoff), slg,
                             -jnp.inf)


def _nucleus_mask(lg, top_p, mesh=None):
    """Mask lg to the top-p nucleus, the survivor set applied as a >=
    threshold on the kept minimum — no full-vocab scatter."""
    rows = lg.shape[:-1] + (1,)
    _, cutoff = thresholds(lg, jnp.full(rows, lg.shape[-1], jnp.int32),
                           jnp.full(rows, top_p, jnp.float32), mesh=mesh)
    return jnp.where(lg >= cutoff, lg, -jnp.inf)


def _draw(key, greedy, row):
    """One token of one chain: ``greedy`` where the row is greedy, else
    ``categorical`` on the masked (V,) row, as :func:`sample_logits`
    draws it from a (1, V) batch."""
    tok = jax.random.categorical(key, row[None], axis=-1)[0] \
        .astype(jnp.int32)
    return jnp.where(greedy >= 0, greedy, tok)


@jax.named_scope(SAMPLER_SCOPE)
def _sample_slots(rngs, lg, temperature, top_k, top_p,
                  mesh: Optional[Mesh] = None):
    """A decode step's sample for a bank of slots, mirroring generate's
    step slot by slot: the two thresholds of every row found at once
    over the (S, V) block, then each slot splits its own chain and
    draws from its own masked row, so equal masks give equal tokens.
    Returns (carry keys (S, 2), tokens (S,))."""
    greedy, masked = _masked_logits(lg, temperature, top_k, top_p, mesh)
    keys = jax.vmap(jax.random.split)(rngs)
    return keys[:, 0], jax.vmap(_draw)(keys[:, 1], greedy, masked)


# ---------------------------------------------------------------------------
# the decode step around a family's logits
# ---------------------------------------------------------------------------
def busiest_share(counts):
    """The busiest experts' loads over a step's assignments (routing
    counts: assignments, experts touched, loads), in millionths, int32."""
    return (counts[2].astype(jnp.float32) * 1e6
            / jnp.maximum(counts[0], 1).astype(jnp.float32)
            ).astype(jnp.int32)


def decode_step(logits, kv, sv, active, temperature, top_k, top_p,
                counts=None, mesh: Optional[Mesh] = None):
    """A decode step's end over its logits (S, V): each slot's token
    drawn down its own chain, the ``active`` slots' lengths advanced;
    a family's routing ``counts`` ride behind the tokens (assignments,
    experts touched, :func:`busiest_share`). Returns (tokens (S,) or
    (S + 3,) int32, kv, new sv)."""
    new_rngs, sampled = _sample_slots(sv["rngs"], logits, temperature,
                                      top_k, top_p, mesh)
    out = sampled
    if counts is not None:
        share = busiest_share(counts)
        out = jnp.concatenate([sampled, counts[:2], share[None]])
    return out, kv, {
        "lengths": sv["lengths"].astype(jnp.int32)
        + active.astype(jnp.int32),
        "tokens": sampled, "rngs": new_rngs}


# ---------------------------------------------------------------------------
# pages: the step's write and the admission's seat
# ---------------------------------------------------------------------------
@jax.named_scope(KV_WRITE_SCOPE)
def write_pages(ck, cv, knew, vnew, layer, phys, off):
    """The new tokens' K/V into the whole (L, n_pages, ps, ...) pools:
    token i of layer ``layer`` at in-page offset ``off[i]`` of page
    ``phys[i]``. One scatter whose indexed dimensions are the pool's
    leading ones, so the donated (and loop-carried) pool is updated in
    place in the layout it is stored in."""
    ck = ck.at[layer, phys, off].set(knew.astype(ck.dtype))
    cv = cv.at[layer, phys, off].set(vnew.astype(cv.dtype))
    return ck, cv


def copy_page(kv, src, dst):
    """Copy pool page ``src`` onto page ``dst`` across every page pool
    (:data:`PAGE_POOLS`) — the engine's copy-on-write fork primitive
    (one compiled program for any src/dst: both are traced scalars).
    Only pool arrays (page axis 1) are touched; the rest passes through
    untouched."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = dict(kv)
    for n in PAGE_POOLS:
        if n in kv:
            a = kv[n]
            page = lax.dynamic_index_in_dim(a, src, axis=1,
                                            keepdims=False)
            out[n] = lax.dynamic_update_index_in_dim(a, page, dst,
                                                     axis=1)
    return out


def seat_pages(pool, rows, pages_row):
    """A stage's rows (L, 1, capacity, width) into the slot's pages of
    the pool (L, n_pages, page_size, width): entries of ``pages_row``
    past the granted ones name scratch page 0, never attended; rows past
    the row's pages are dropped."""
    ps = pool.shape[2]
    rows = rows[:, 0]
    rows = jnp.pad(rows, ((0, 0), (0, -rows.shape[1] % ps), (0, 0)))
    pages = rows.reshape(rows.shape[0], -1, ps, rows.shape[-1])
    n = min(pages.shape[1], pages_row.shape[0])
    return pool.at[:, pages_row[:n]].set(pages[:, :n].astype(pool.dtype))


@jax.named_scope(KV_WRITE_SCOPE)
def seat_pools(kv, state, pages_row, slot=None):
    """The state seat of a family whose state is its pages: every pool
    the stage holds rows of, seated (:func:`seat_pages`)."""
    del slot
    return {n: seat_pages(kv[n], rows, pages_row)
            for n, rows in state.items()}


# ---------------------------------------------------------------------------
# admission
# ---------------------------------------------------------------------------
def last_logits(final, cfg, params, x, n_valid):
    """A run's ``head``: the logits ``final(cfg, params, x)`` of position
    ``n_valid - 1`` of x (1, s, dim), (1, V) float32, when the end asks
    (a chunk that is not the last never does)."""
    return lambda: final(cfg, params, lax.dynamic_slice_in_dim(
        x, n_valid - 1, 1, axis=1))[:, 0]


def _seat_first(seat, cfg, head, state, true_len, pages_row, slot, kv, sv,
                rng, temperature, top_k, top_p, *, vectors_first=False):
    """The end of a family whose prompt yields its first token: that
    token sampled from ``head()`` on the request's chain, the prompt's
    ``state`` seated by the family's ``seat(kv, state, pages_row, slot)
    -> kv``, the slot's vectors set; ``vectors_first`` sets them before
    the seat (no value changes; the order is the family's program's).
    Returns (first token (1,), new kv, new sv)."""
    del cfg
    logits = head()
    rng, sub = jax.random.split(rng)
    tok = sample_logits(sub, logits, temperature=temperature,
                        top_k=top_k, top_p=top_p)
    if vectors_first:
        new_sv = seat_slot(sv, slot, true_len, tok, rng)
        return tok, seat(kv, state, pages_row, slot), new_sv
    new_kv = seat(kv, state, pages_row, slot)
    return tok, new_kv, seat_slot(sv, slot, true_len, tok, rng)


def prefill_whole(run, end, cfg, params, tokens, true_len, prefix_len,
                  pages_row, slot, kv, sv, rng, temperature, top_k, top_p,
                  mesh=None):
    """A family's ``prefill_slot_paged`` (``llama``'s arguments), cold
    only (``prefix_len`` 0): the run over the prompt END-padded to its
    bucket from a fresh stage, then the end."""
    del prefix_len
    true_len = jnp.asarray(true_len, jnp.int32)
    head, state = run(cfg, params, tokens, 0, true_len, None, mesh)
    return end(cfg, head, state, true_len, pages_row,
               jnp.asarray(slot, jnp.int32), kv, sv, rng, temperature,
               top_k, top_p)


def prefill_chunk(run, cfg, params, tokens, start, stage, mesh=None):
    """A family's ``prefill_slot_paged_chunk``: a whole chunk (1, chunk)
    of a prompt, not its last, at ``start ..`` (0: from nothing, whatever
    the stage holds), to the stage the next chunk goes on from; the slot
    bank is not touched."""
    return run(cfg, params, tokens, jnp.asarray(start, jnp.int32),
               tokens.shape[1], stage, mesh)[1]


def prefill_last(run, end, cfg, params, tokens, start, n_valid, stage,
                 pages_row, slot, kv, sv, rng, temperature, top_k, top_p,
                 mesh=None):
    """A family's ``prefill_slot_paged_last``: a prompt's last chunk,
    ``n_valid`` tokens END-padded to (1, chunk) at ``start ..``, from the
    stage; then the end, at the prompt's length ``start + n_valid``."""
    start = jnp.asarray(start, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    head, state = run(cfg, params, tokens, start, n_valid, stage, mesh)
    return end(cfg, head, state, start + n_valid, pages_row,
               jnp.asarray(slot, jnp.int32), kv, sv, rng, temperature,
               top_k, top_p)
