"""A decoder of power-retention layers (Manifest AI's Brumby,
``model_type: brumby``; "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239): the Qwen3 block with its softmax attention replaced
by a gated linear-attention recurrence whose kernel is ``(q . k)^2``
(``ops/retention.py`` has the equations and the state's layout).

Every layer is ``x += retention(RMSNorm(x)) W_o; x += SwiGLU(RMSNorm(
x))``: ``q = RoPE(RMSNorm_head(h W_q))``, ``k = RoPE(RMSNorm_head(h
W_k))``, ``v = h W_v`` (grouped: query head ``i`` reads KV head ``i //
(n_heads / n_kv_heads)``), and a gate a KV head and token, ``log g =
logsigmoid(h W_g + b_g)`` in float32, so that one state serves a KV
head's query heads. NO layer holds keys or values: a sequence's whole
cache is ``(S, z)``, ``n_kv_heads x F x (head_dim + 1)`` values a layer
(F = ``ops.retention.sympow2_rows(head_dim)``), whatever its length.

The serving surface is ``llama.py``'s (``init_params``, ``forward``,
``init_paged_cache``, ``decode_attention_path``, ``decode_slots_paged``,
``prefill_slot_paged``, ``copy_page``) plus sambay's chunk surface
(``init_prefill_stage``, ``prefill_slot_paged_chunk``, ``.._last``: the
stage carries ``(S, z)`` from chunk to chunk, and the last chunk seats
it in the slot). The state has no pages: the engine admits by free
slots, and the page table its programs are handed is empty and unread.
RMSNorm, RoPE, the embedding, the SwiGLU, the output projection, the
head and the sampler are ``llama.py``'s own functions.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.retention import (GATE_SCOPE, STATE_SCOPE, retention_chunk,
                             retention_step_bank, retention_step_path,
                             sympow2_rows)
from . import llama
from .llama import rms_norm

__all__ = ["RetentionConfig", "CONFIGS", "init_params", "forward",
           "layer_streams", "layer_keys", "init_paged_cache",
           "prefill_slot_paged", "init_prefill_stage",
           "prefill_slot_paged_chunk", "prefill_slot_paged_last",
           "decode_logits", "decode_slots_paged", "copy_page",
           "decode_attention_path"]

# the named scopes of this family's programs: ``retention_state``,
# ``retention_intra`` (ops/retention.py) and ``retention_gate`` beside
# llama.py's embed, norm, qkv_proj, rope, out_proj, mlp, lm_head, sampler

# what ``ServeEngine`` cannot do for this family yet, by option, with
# the mechanism in the way (the engine raises with these words)
SERVE_UNSUPPORTED = {
    "prefix_cache": "a shared prefix is a snapshot of (S, z) at its "
                    "boundary, and no program takes one: the state a "
                    "slot holds has already folded in every later token",
    "speculate_k": "a rejected draft has to be rolled back out of the "
                   "state, which keeps no copy of itself from before "
                   "the drafted tokens",
    "int8_pages": "the state has no pages, and no quantised form of a "
                  "float32 running sum is written down",
    "submit_prefilled": "a disaggregated hand-off carries per-token keys "
                        "and values, not a retention state",
    "mesh": "no sharding rules for the state yet (a chip's share of the "
            "KV heads)",
}
# which kind of state each donated array is (the engine's byte gauges);
# neither kind ends in ``_pages``: no part of the state grows with tokens
STATE_KINDS = {"S": "retention_state", "z": "retention_state"}

_F32 = jnp.float32


@dataclass(frozen=True)
class RetentionConfig:
    family: ClassVar[str] = "retention"
    # llama._ffn's switch between its SwiGLU and an expert bank
    moe_experts: ClassVar[int] = 0
    # the kernel's degree: ``ops.retention`` computes the symmetric
    # SQUARE and no other power
    power: ClassVar[int] = 2
    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    hidden_dim: int = 17408
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq_len: int = 32768
    tie_embeddings: bool = False
    # what the published config does not give (the benchmark's
    # configuration lists each under ``assumed``)
    gate_bias: Tuple[float, float] = (4.0, 8.0)   # b_g ~ U(lo, hi)
    # tokens a ``retention_chunk`` call runs: a longer sequence (a
    # ``forward``, a check's ``layer_streams``, a whole-prompt prefill)
    # goes through in pieces of this many. The engine's ``prefill_chunk``
    # hands its programs at most that many tokens, which the cells' 1024
    # takes whole; a field only because the toy tests set 16, so that a
    # 40-token prompt crosses pieces
    chunk: int = 1024
    dtype: Any = jnp.bfloat16         # activations
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32    # what (S, z) is held in

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads is a multiple of n_kv_heads")

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def state_rows(self) -> int:      # F: rows of S a KV head
        return sympow2_rows(self.head_dim)


CONFIGS = {
    # the published ratios at toy widths: 10 query heads over 2 KV
    # heads of 16 (F = 144), chunks of 16
    "tiny": RetentionConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=10, n_kv_heads=2,
        head_dim=16, hidden_dim=160, max_seq_len=256, chunk=16,
        dtype=jnp.float32, param_dtype=jnp.float32),
    "brumby_14b": RetentionConfig(),
}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: RetentionConfig, rng: Optional[jax.Array] = None):
    """``llama.init_params``'s tree (the block's projections, SwiGLU and
    norms stacked on a leading layer axis, embedding, head) plus, a
    layer: ``q_norm``/``k_norm`` (head_dim), the gate's ``wg`` (dim,
    n_kv_heads) and its bias ``bg`` (n_kv_heads) float32, uniform over
    ``cfg.gate_bias``: ``g = sigmoid(h W_g + b_g)`` then lies in
    0.98-0.9997 and the state remembers 50-3000 tokens, as a trained
    gate does; with random ``W_g`` alone ``g`` is about 0.5 and the
    state forgets in ten tokens. ``(0, 0)`` is the bias-free layer."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    k_llama, k_gate, k_bias = jax.random.split(rng, 3)
    params = llama.init_params(cfg, k_llama)
    L, G, d = cfg.n_layers, cfg.n_kv_heads, cfg.param_dtype
    lo, hi = cfg.gate_bias
    params["layers"].update(
        q_norm=jnp.ones((L, cfg.head_dim), d),
        k_norm=jnp.ones((L, cfg.head_dim), d),
        wg=jax.random.normal(k_gate, (L, cfg.dim, G), d)
        / math.sqrt(cfg.dim),
        bg=jax.random.uniform(k_bias, (L, G), _F32, lo, hi))
    return params


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
def _rope_tables(cfg: RetentionConfig, positions):
    """cos and sin of ``positions`` (b, s) -> (b, 1, s, head_dim / 2),
    as ``llama.apply_rope`` takes them beside (b, heads, s, head_dim)."""
    hd = cfg.head_dim
    inv_freq = 1.0 / (cfg.rope_theta **
                      (jnp.arange(0, hd, 2, dtype=_F32) / hd))
    freqs = positions.astype(_F32)[:, None, :, None] * inv_freq
    return jnp.cos(freqs), jnp.sin(freqs)


def _project(cfg: RetentionConfig, lp, h, cos, sin, valid):
    """h (b, s, dim) -> q (b, H, s, hd) and k (b, G, s, hd), each head
    normed and rotated; v (b, G, s, hd); log_g (b, G, s) float32.
    Where ``valid`` (b, s) is false the position is no key and decays
    nothing (``k = 0``, ``log_g = 0``)."""
    b, s, _ = h.shape
    hd = cfg.head_dim
    with jax.named_scope("qkv_proj"):
        heads = lambda w, n: (h @ w).reshape(b, s, n, hd).transpose(
            0, 2, 1, 3)
        q = heads(lp["wq"], cfg.n_heads)
        k = heads(lp["wk"], cfg.n_kv_heads)
        v = heads(lp["wv"], cfg.n_kv_heads)
    q = llama.apply_rope(rms_norm(q, lp["q_norm"], cfg.norm_eps), cos, sin)
    k = llama.apply_rope(rms_norm(k, lp["k_norm"], cfg.norm_eps), cos, sin)
    with jax.named_scope(GATE_SCOPE):
        logit = jnp.einsum("bsd,dg->bgs", h, lp["wg"],
                           preferred_element_type=_F32)
        log_g = jax.nn.log_sigmoid(logit + lp["bg"].astype(_F32)[:, None])
        log_g = jnp.where(valid[:, None], log_g, 0.0)
        k = jnp.where(valid[:, None, :, None], k, jnp.zeros((), k.dtype))
    return q, k, v, log_g


def _scan_layers(cfg: RetentionConfig, params, x, state, cos, sin, valid,
                 retain):
    """Every layer over x (b, s, dim), one ``lax.scan``, so the program
    does not grow with depth. ``state`` = (S (L, b, G, hd, F), z (L, b,
    G, F)) is carried WHOLE and each layer reads and writes its own
    slice by index (cut into slices by the scan, the state would be
    held twice). ``retain(q, k, v, log_g, S, z, layer) -> (y (b, H, s,
    hd), S, z)`` is the retention on layer ``layer`` of the state.
    Returns (x, state, what only a check reads: every layer's ``x``
    (L, b, s, dim), the stream entering it, and ``k``, ``v`` (L, b, s,
    G, hd) and ``log_g`` (L, b, s, G), what its retention was handed;
    a program that returns none of them computes none)."""
    def body(carry, xs):
        x, S, z = carry
        lp, layer = xs
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v, log_g = _project(cfg, lp, h, cos, sin, valid)
        y, S, z = retain(q, k, v, log_g, S, z, layer)
        out = x + llama._out_proj(cfg, lp, y.astype(cfg.dtype))
        h = rms_norm(out, lp["ffn_norm"], cfg.norm_eps)
        delta, _ = llama._ffn(cfg, lp, h, None)
        return (out + delta, S, z), {
            "x": x, "k": jnp.swapaxes(k, 1, 2), "v": jnp.swapaxes(v, 1, 2),
            "log_g": jnp.swapaxes(log_g, 1, 2)}

    (x, S, z), seen = lax.scan(
        body, (x,) + tuple(state),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return x, (S, z), seen


def _final(cfg, params, x):
    return llama._lm_head(cfg, params, rms_norm(x, params["final_norm"],
                                                cfg.norm_eps))


# ---------------------------------------------------------------------------
# whole sequences: forward and the prefills (the chunked form)
# ---------------------------------------------------------------------------
def _empty_state(cfg: RetentionConfig, b: int):
    """(S, z) of ``b`` sequences that have seen nothing."""
    lead = (cfg.n_layers, b, cfg.n_kv_heads)
    return (jnp.zeros(lead + (cfg.head_dim, cfg.state_rows),
                      cfg.state_dtype),
            jnp.zeros(lead + (cfg.state_rows,), cfg.state_dtype))


def _sequence_layers(cfg: RetentionConfig, params, tokens, start, state,
                     n_valid):
    """Every layer over tokens (b, s) at positions ``start ..`` (a
    traced scalar) from ``state``, ``cfg.chunk`` tokens to a
    ``retention_chunk`` call (the state hands each piece on to the
    next). Tokens from ``n_valid`` on are padding: no keys, no decay.
    Returns (x (b, s, dim), the state after token ``n_valid - 1``,
    ``_scan_layers``' arrays for a check, over the whole sequence)."""
    b, s = tokens.shape
    start = jnp.asarray(start, jnp.int32)

    def retain(q, k, v, log_g, S, z, layer):
        with jax.named_scope(STATE_SCOPE):
            at = lambda a: lax.dynamic_index_in_dim(a, layer, 0,
                                                    keepdims=False)
            Sl, zl = at(S), at(z)
        y, Sl, zl = retention_chunk(q, k, v, log_g, Sl, zl, scale=cfg.scale)
        with jax.named_scope(STATE_SCOPE):
            return (y, lax.dynamic_update_index_in_dim(S, Sl, layer, 0),
                    lax.dynamic_update_index_in_dim(z, zl, layer, 0))
    xs, seens = [], []
    for c0 in range(0, s, cfg.chunk):
        piece = tokens[:, c0:c0 + cfg.chunk]
        at = c0 + jnp.arange(piece.shape[1], dtype=jnp.int32)
        cos, sin = _rope_tables(
            cfg, jnp.broadcast_to(start + at, piece.shape))
        x, state, seen = _scan_layers(
            cfg, params, llama._embed(cfg, params, piece), state, cos, sin,
            jnp.broadcast_to(at < n_valid, piece.shape), retain)
        xs.append(x)
        seens.append(seen)
    return (jnp.concatenate(xs, axis=1), state, jax.tree.map(
        lambda *pieces: jnp.concatenate(pieces, axis=2), *seens))


def forward(cfg: RetentionConfig, params, tokens):
    """tokens (b, s) -> logits (b, s, V) float32: every layer on every
    position, from an empty state."""
    b, s = tokens.shape
    x, _, _ = _sequence_layers(cfg, params, tokens, 0,
                               _empty_state(cfg, b), s)
    return _final(cfg, params, x)


def layer_streams(cfg: RetentionConfig, params, tokens):
    """The residual stream entering every layer, and leaving the last,
    in a pass like :func:`forward`'s: (L + 1, b, s, dim). What a check
    holds each layer's own arithmetic against a reference with, one
    layer at a time."""
    b, s = tokens.shape
    x, _, seen = _sequence_layers(cfg, params, tokens, 0,
                                  _empty_state(cfg, b), s)
    return jnp.concatenate([seen["x"], x[None]])


def layer_keys(cfg: RetentionConfig, params, tokens):
    """What every layer's retention was handed in a pass like
    :func:`forward`'s: keys and values (L, b, s, G, hd) in the
    activations' type, normed and rotated, and ``log_g`` (L, b, s, G)
    float32. What a check holds the sums a state keeps against a
    reference with, on the program's own inputs."""
    b, s = tokens.shape
    _, _, seen = _sequence_layers(cfg, params, tokens, 0,
                                  _empty_state(cfg, b), s)
    return seen["k"], seen["v"], seen["log_g"]


# ---------------------------------------------------------------------------
# serving state and programs
# ---------------------------------------------------------------------------
def decode_attention_path(cfg, kv, mesh=None, *, verify: bool = False) -> str:
    """Which form the decode program runs over the state ``kv`` (arrays
    or shapes): ``ops.retention.retention_step_path``'s answer for the
    bank, as ``"state_kernel"`` (the Pallas kernel: a layer's state read
    once and written once a step) or ``"state"`` (the ``jnp`` form).
    Either way no keys or values are read, whatever the length. (A
    ``mesh`` never gets here: ``init_paged_cache`` refuses one.)"""
    del cfg, mesh, verify
    path = retention_step_path(kv["S"].shape, kv["S"].dtype)
    return "state_kernel" if path == "kernel" else "state"


def init_paged_cache(cfg: RetentionConfig, max_slots: int, n_pages: int,
                     page_size: int, mesh=None, int8: bool = False):
    """Device state for the serving engine: ``S`` (L, slots, n_kv_heads,
    head_dim, F) and ``z`` (L, slots, n_kv_heads, F) in
    ``cfg.state_dtype``, a fixed block a slot whatever its sequence's
    length, plus the per-slot ``lengths``/``tokens``/``rngs`` of every
    family. There is no page pool: ``n_pages`` and ``page_size`` size
    nothing here."""
    del n_pages, page_size
    if mesh is not None or int8:
        raise ValueError("retention: " + SERVE_UNSUPPORTED[
            "mesh" if mesh is not None else "int8_pages"])
    S, z = _empty_state(cfg, max_slots)
    return {
        "S": S, "z": z,
        "lengths": jnp.zeros((max_slots,), jnp.int32),
        "tokens": jnp.zeros((max_slots,), jnp.int32),
        "rngs": jnp.zeros((max_slots, 2), jnp.uint32)}


def copy_page(kv, src, dst):
    """``llama.copy_page``'s place in the surface: the state has no
    pages, so nothing is copied."""
    del src, dst
    return kv


def decode_logits(cfg: RetentionConfig, params, kv, sv, active):
    """The decode step up to its logits: (logits (S, V) float32, the
    state with the step's token folded in). A slot that is not
    ``active`` flows through (fixed shape) and keeps its state as it
    was."""
    pos = sv["lengths"].astype(jnp.int32)[:, None]
    cos, sin = _rope_tables(cfg, pos)

    def retain(q, k, v, log_g, S, z, layer):
        y, S, z = retention_step_bank(
            q[:, :, 0], k[:, :, 0], v[:, :, 0], log_g[:, :, 0], S, z, layer,
            scale=cfg.scale)
        return y[:, :, None], S, z

    x, (S, z), _ = _scan_layers(
        cfg, params, llama._embed(cfg, params, sv["tokens"][:, None]),
        (kv["S"], kv["z"]), cos, sin, active[:, None], retain)
    return _final(cfg, params, x)[:, 0], {"S": S, "z": z}


def decode_slots_paged(cfg: RetentionConfig, params, kv, sv, active,
                       page_table, temperature, top_k, top_p, mesh=None):
    """ONE decode step over the bank: ``llama.decode_slots_paged``'s
    contract (same arguments, same sampling and rng chains). Each slot's
    state is read and written where it lies; ``page_table`` is the
    engine's empty one. Returns (sampled tokens (S,), new kv, new sv)."""
    del page_table, mesh
    logits, kv = decode_logits(cfg, params, kv, sv, active)
    new_rngs, sampled = llama._sample_slots(
        sv["rngs"], logits, temperature, top_k, top_p)
    return sampled, kv, {
        "lengths": sv["lengths"].astype(jnp.int32)
        + active.astype(jnp.int32),
        "tokens": sampled, "rngs": new_rngs}


def _seat_first(cfg, params, x, state, n_valid, true_len, slot, kv, sv,
                rng, temperature, top_k, top_p):
    """The end of an admission: the logits of position ``n_valid - 1``
    of x (1, s, dim), the first token sampled from them, the prompt's
    state seated over whatever the slot held, the slot's length, token
    and rng chain set. Returns (first token (1,), new kv, new sv)."""
    last = lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = _final(cfg, params, last)[:, 0]
    rng, sub = jax.random.split(rng)
    tok = llama.sample_logits(sub, logits, temperature=temperature,
                              top_k=top_k, top_p=top_p)
    with jax.named_scope(STATE_SCOPE):
        new_kv = {n: lax.dynamic_update_slice_in_dim(
            kv[n], a.astype(kv[n].dtype), slot, axis=1)
            for n, a in zip(("S", "z"), state)}
    z = jnp.zeros((), jnp.int32)
    new_sv = {
        "lengths": lax.dynamic_update_slice(
            sv["lengths"].astype(jnp.int32), true_len[None], (slot,)),
        "tokens": lax.dynamic_update_slice(
            sv["tokens"], tok.astype(sv["tokens"].dtype), (slot,)),
        "rngs": lax.dynamic_update_slice(
            sv["rngs"], rng[None].astype(sv["rngs"].dtype), (slot, z))}
    return tok, new_kv, new_sv


def prefill_slot_paged(cfg: RetentionConfig, params, tokens, true_len,
                       prefix_len, pages_row, slot, kv, sv, rng,
                       temperature, top_k, top_p, mesh=None):
    """Admission: ``llama.prefill_slot_paged``'s contract, cold only
    (``prefix_len`` is 0: the engine refuses a prefix cache for this
    family). Every layer over the prompt (END-padded to its bucket; the
    padding moves no state), the head on the last position alone; the
    slot's state is overwritten whole. Returns (first token (1,), new
    kv, new sv)."""
    del prefix_len, pages_row, mesh
    true_len = jnp.asarray(true_len, jnp.int32)
    x, state, _ = _sequence_layers(cfg, params, tokens, 0,
                                   _empty_state(cfg, 1), true_len)
    return _seat_first(cfg, params, x, state, true_len, true_len,
                       jnp.asarray(slot, jnp.int32), kv, sv, rng,
                       temperature, top_k, top_p)


# -- a prompt in chunks: the stall a running request sees is one chunk's ----
def init_prefill_stage(cfg: RetentionConfig, capacity: int, chunk: int):
    """Where a prompt that is prefilled ``chunk`` tokens at a time keeps
    its state between chunks, outside the slot bank (a decode step in
    between runs over every slot): one sequence's ``(S, z)``, whatever
    ``capacity`` is."""
    del capacity, chunk
    S, z = _empty_state(cfg, 1)
    return {"S": S, "z": z}


def _from_stage(stage, start):
    """The stage's state, or an empty one for a prompt's first chunk
    (the stage still holds the prompt before it)."""
    return tuple(jnp.where(start > 0, stage[n], jnp.zeros((), stage[n].dtype))
                 for n in ("S", "z"))


def prefill_slot_paged_chunk(cfg: RetentionConfig, params, tokens, start,
                             stage, mesh=None):
    """One whole chunk of a prompt that is not its last: every layer
    over tokens (1, chunk) at positions ``start ..``, from the state the
    chunks before left in the stage (``start`` 0: from nothing) to the
    state the next chunk goes on from. The slot bank is not touched."""
    del mesh
    start = jnp.asarray(start, jnp.int32)
    _, (S, z), _ = _sequence_layers(cfg, params, tokens, start,
                                    _from_stage(stage, start),
                                    tokens.shape[1])
    return {"S": S, "z": z}


def prefill_slot_paged_last(cfg: RetentionConfig, params, tokens, start,
                            n_valid, stage, pages_row, slot, kv, sv, rng,
                            temperature, top_k, top_p, mesh=None):
    """A prompt's last chunk, ``n_valid`` tokens END-padded to tokens
    (1, chunk), at positions ``start ..``; then the admission's end as
    ``prefill_slot_paged``'s: the prompt's state seated into the slot,
    the first token sampled. Returns (first token (1,), new kv, new
    sv)."""
    del pages_row, mesh
    start = jnp.asarray(start, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    x, state, _ = _sequence_layers(cfg, params, tokens, start,
                                   _from_stage(stage, start), n_valid)
    return _seat_first(cfg, params, x, state, n_valid, start + n_valid,
                       jnp.asarray(slot, jnp.int32), kv, sv, rng,
                       temperature, top_k, top_p)
