"""A decoder of latent-attention (MLA) layers and routed experts, as
DeepSeek-V2/V3 wrote them down (arXiv 2405.04434 section 2.1, 2412.19437
section 2.1; ``model_type: deepseek_v3``), in the form without a query
latent (``q_lora_rank: null``).

Every layer is ``x += attn(RMSNorm(x)); x += ffn(RMSNorm(x))``.

*Attention.* ``q = x W_q`` is split per head into ``q_nope`` and
``q_rope``; ``[c_kv, k_rope] = x W_kva``; ``c = RMSNorm(c_kv)``; RoPE on
``q_rope`` and on ``k_rope``, which is ONE vector a token, shared by all
heads; ``[k_nope, v] = c W_kvb`` per head. A token's cache row is ``[c;
rotated k_rope]`` (``kv_lora_rank + qk_rope_head_dim`` values a layer)
and nothing else. Two forms of one attention:

- *decompressed* (whole sequences: ``forward``, every prefill): keys
  and values are rebuilt from the rows a block of keys at a time,
  ``score = (q_nope . k_nope + q_rope . k_rope) / sqrt(qk_head_dim)``,
  causal online softmax in float32, ``o = P v``;
- *absorbed* (the decode step, over the pages): ``q_lat = q_nope
  W_kvb^K`` (a head's query in the latent space), ``score = q_lat . c
  + q_rope . k_rope``, ``o_lat = P c``, ``o = o_lat W_kvb^V``: every
  head reads the same row, which is both key and (its first
  ``kv_lora_rank`` values) value; on a TPU one Pallas kernel a layer
  walks each slot's live pages (``ops.paged_attention.
  paged_latent_pages``).

*Feed-forward.* The first ``first_k_dense`` layers are a SwiGLU of
``hidden_dim``; every other layer is ``sum_i w_i E_i(x) + S(x)``: a
sigmoid router with a selection bias picks ``experts_per_tok`` of
``n_routed_experts`` SwiGLU experts (``parallel.moe.route_sigmoid``), a
dropless dispatch runs each chosen expert on its tokens
(``parallel.moe.moe_ffn_dropless``: sort, grouped product, unsort), and
``S`` is one SwiGLU of ``n_shared_experts x moe_hidden_dim``.

The module keeps ``models/serving.py``'s family contract, prompts in
chunks included: a chunk's rows wait in the stage, where the chunks
after it read them, and the last chunk seats the whole prompt into the
slot's pages (``serving.seat_pools``). The decode
program also counts, on the device, what the router did with the step's
tokens (``STEP_COUNTS``), and hands the counts out behind the sampled
tokens in the one array the engine reads back.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (MLA_SCOPE, latent_decode_path,
                             latent_prefill_attention,
                             paged_latent_decode_attention)
from ..parallel import moe
from . import llama, serving
from .llama import final_logits, rms_norm
from .serving import KV_WRITE_SCOPE, kv_block, normal

__all__ = ["LatentMoEConfig", "CONFIGS", "init_params", "forward",
           "init_paged_cache", "prefill_slot_paged", "init_prefill_stage",
           "prefill_slot_paged_chunk", "prefill_slot_paged_last",
           "decode_slots_paged", "copy_page", "decode_attention_path",
           "router_picks", "layer_streams", "STEP_COUNTS"]

# the named scopes of this family's programs besides llama.py's (embed,
# norm, qkv_proj, rope, kv_write, kv_gather, out_proj, mlp — the leading
# dense layers' —, lm_head, sampler): ``mla_attention``
# (ops/attention.py: the absorption, scores and values of a decode step;
# the decompression and the attention of a prefill), the four
# ``moe_*`` in parallel/moe.py. Each is opened at the top level of its
# layer, so an operation's scope is the first name of its path.

# what ``ServeEngine`` cannot do for this family yet, by option, with
# the mechanism in the way (the engine raises with these words)
SERVE_UNSUPPORTED = {
    "prefix_cache": "a prefix hit over latent pages needs a prefill that "
                    "starts from rows already in the pool, and this "
                    "family's chunks read the rows before them from the "
                    "stage only",
    "speculate_k": "the absorbed decode attention has no verify step "
                   "with a length per drafted query",
    "int8_pages": "a latent row is normalised and shared by every head: "
                  "no quantised form of it is written down yet",
    "submit_prefilled": "a disaggregated hand-off carries per-head keys "
                        "and values, not latent rows",
    "mesh": "no sharding rules for the expert bank or the latent pool "
            "yet (a chip's share of the experts and the ep exchange)",
}
# which kind of state each donated array is (the engine's byte gauges)
STATE_KINDS = {"latent": "latent_pages"}
# what the decode program counts on the device, a step at a time, over
# the tokens of the slots that ran: the values ride behind the sampled
# tokens in the program's first output, in this order, as int32. An
# entry with ``buckets`` is a histogram of value / ``per``, any other a
# counter the value is added to.
STEP_COUNTS = (
    {"name": "serve_moe_assignments_total",
     "help": "Token-to-expert assignments of the decode steps, summed "
             "over the expert layers"},
    {"name": "serve_moe_experts_touched_total",
     "help": "Experts that got at least one token in a decode step, "
             "summed over the expert layers"},
    {"name": "serve_moe_load_max_share",
     "help": "The busiest expert's share of a decode step's assignments "
             "(the expert layers' busiest loads over the step's "
             "assignments)",
     "buckets": (0.01, 0.02, 0.03, 0.04, 0.05, 0.06, 0.08, 0.1, 0.15, 0.2,
                 0.3, 0.5, 1.0), "per": 1_000_000},
)

_F32 = jnp.float32


@dataclass(frozen=True)
class LatentMoEConfig:
    family: ClassVar[str] = "latent_moe"
    vocab_size: int = 128256
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    hidden_dim: int = 6144           # the leading dense layers' SwiGLU
    first_k_dense: int = 1
    moe_hidden_dim: int = 768
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    experts_per_tok: int = 6
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq_len: int = 32768
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16        # activations
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 1 <= self.first_k_dense < self.n_layers:
            raise ValueError(
                "a leading dense layer and an expert layer at least: "
                f"first_k_dense {self.first_k_dense} of {self.n_layers}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("RoPE pairs up qk_rope_head_dim's columns")
        if self.experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts a token than experts")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_dim(self) -> int:        # a token's cache row, a layer
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_stored(self) -> int:
        """The row as the pool and the stage store it: zero-padded to
        whole lane tiles of 128 values (576 -> 640). A v5e lays a
        576-wide array out with its PAGE dimension minor, and the
        decode program then copies the whole pool into row-major order
        and back around its one-token write (AOT compile, PR 31: 1.68
        GB of temporaries, two pool-sized copies a step)."""
        return -(-self.row_dim // 128) * 128

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_head_dim)


CONFIGS = {
    # the published ratios kept odd at toy widths: 4 heads of 24 + 8
    # (values 16), latent 32 + rope 8 = a row of 40, 16 experts top-3,
    # one dense layer and two expert layers
    "tiny": LatentMoEConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
        hidden_dim=160, moe_hidden_dim=48, n_routed_experts=16,
        n_shared_experts=2, experts_per_tok=3, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32),
    "kanana2_30b_a3b": LatentMoEConfig(),
}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_attn(key, cfg: LatentMoEConfig, n: int):
    ks = jax.random.split(key, 4)
    d, D, H, R = cfg.param_dtype, cfg.dim, cfg.n_heads, cfg.kv_lora_rank
    return {
        "attn_norm": jnp.ones((n, D), d), "ffn_norm": jnp.ones((n, D), d),
        "kv_norm": jnp.ones((n, R), d),
        "wq": normal(ks[0], (n, D, H * cfg.qk_head_dim), D, d),
        "wkva": normal(ks[1], (n, D, cfg.row_dim), D, d),
        # per head [k_nope; v], as the published kv_b_proj lays it out
        "wkvb": normal(ks[2], (n, R, H * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)), R, d),
        "wo": normal(ks[3], (n, H * cfg.v_head_dim, D),
                      H * cfg.v_head_dim * 2 * cfg.n_layers, d)}


def _init_swiglu(key, D, F, down_fan, dtype, lead=(), prefix="w"):
    k1, k2, k3 = jax.random.split(key, 3)
    return {prefix + "_gate": normal(k1, lead + (D, F), D, dtype),
            prefix + "_up": normal(k2, lead + (D, F), D, dtype),
            prefix + "_down": normal(k3, lead + (F, D), down_fan, dtype)}


def init_params(cfg: LatentMoEConfig, rng: Optional[jax.Array] = None):
    """Random weights, scaled by fan-in. ``dense`` holds the leading
    dense layers and ``moe`` the expert layers, each kind stacked on a
    leading axis; the expert bank is three arrays of (layers, experts,
    ..), which the grouped product takes as they are stored. The
    router's selection bias is float32, normal x 0.01: selection and
    weight differ, as in a trained checkpoint, and the load stays as
    even as a trained bias keeps it (normal x 0.1 is as wide as the top
    scores' spread and sends most tokens to the same experts: 63 of 128
    touched by a 31-token step on the v5e, PR 31, where an even load
    touches 98)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 9)
    d, D = cfg.param_dtype, cfg.dim
    nd, nm, E = cfg.first_k_dense, cfg.n_moe_layers, cfg.n_routed_experts
    F, Fs = cfg.moe_hidden_dim, cfg.n_shared_experts * cfg.moe_hidden_dim
    fan = 2 * cfg.n_layers
    dense = _init_attn(ks[0], cfg, nd)
    dense.update(_init_swiglu(ks[1], D, cfg.hidden_dim,
                              cfg.hidden_dim * fan, d, (nd,)))
    layers = _init_attn(ks[2], cfg, nm)
    layers.update(_init_swiglu(ks[3], D, F, F * fan, d, (nm, E)))
    layers.update(_init_swiglu(ks[4], D, Fs, Fs * fan, d, (nm,), "ws"))
    layers["router"] = normal(ks[5], (nm, D, E), D, d)
    layers["router_bias"] = 0.01 * jax.random.normal(ks[6], (nm, E), _F32)
    params = {"tok_embed": normal(ks[7], (cfg.vocab_size, D), D, d),
              "dense": dense, "moe": layers,
              "final_norm": jnp.ones((D,), d)}
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(ks[8], (D, cfg.vocab_size), D, d)
    return params


# ---------------------------------------------------------------------------
# the pieces of a layer
# ---------------------------------------------------------------------------
def _rope_tables(cfg: LatentMoEConfig, positions):
    """cos and sin of ``positions`` (any shape) -> (.., rope/2). The
    pairing is rotate-half: column i with column i + rope/2."""
    hd = cfg.qk_rope_head_dim
    inv_freq = 1.0 / (cfg.rope_theta **
                      (jnp.arange(0, hd, 2, dtype=_F32) / hd))
    freqs = positions.astype(_F32)[..., None] * inv_freq
    return jnp.cos(freqs), jnp.sin(freqs)


def _latent_qkv(cfg: LatentMoEConfig, lp, h, cos, sin):
    """h (b, s, dim), cos/sin (b, s, rope/2) -> q_nope (b, H, s, nope),
    q_rope (b, H, s, rope) rotated, and the tokens' cache rows (b, s,
    row_stored) = [RMSNorm(c_kv); rotated k_rope; zeros]."""
    b, s, _ = h.shape
    H, R = cfg.n_heads, cfg.kv_lora_rank
    with jax.named_scope("qkv_proj"):
        q = (h @ lp["wq"]).reshape(b, s, H, cfg.qk_head_dim)
        q = q.transpose(0, 2, 1, 3)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_rope = q[..., cfg.qk_nope_head_dim:]
        kva = h @ lp["wkva"]
    c = rms_norm(kva[..., :R], lp["kv_norm"], cfg.norm_eps)
    q_rope = llama.apply_rope(q_rope, cos[:, None], sin[:, None])
    k_rope = llama.apply_rope(kva[:, None, :, R:], cos[:, None],
                              sin[:, None])[:, 0]
    with jax.named_scope("rope"):
        pad = jnp.zeros((b, s, cfg.row_stored - cfg.row_dim), c.dtype)
        rows = jnp.concatenate([c, k_rope, pad], axis=-1)
    return q_nope, q_rope, rows


def _wkvb(cfg: LatentMoEConfig, lp):
    """W_kvb as (rank, H, nope + v)."""
    return lp["wkvb"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                              cfg.qk_nope_head_dim + cfg.v_head_dim)


@jax.named_scope("out_proj")
def _out_proj(cfg: LatentMoEConfig, lp, o):
    """o (b, H, s, v) -> (b, s, dim)."""
    b, H, s, v = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, H * v) @ lp["wo"]


@jax.named_scope("mlp")
def _dense_ffn(lp, h):
    return (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def _moe_ffn(cfg: LatentMoEConfig, lp, bank, layer, h, valid):
    """The expert layer on h (b, s, dim): (delta (b, s, dim), counts
    (3,) int32 = assignments, experts touched, the busiest expert's
    load; the router's picks (b s, top_k)). ``bank`` is the whole
    stack's expert bank and ``layer`` this
    layer's place in it; ``valid`` (b, s) bool says which tokens are
    real (the others are routed nowhere)."""
    b, s, D = h.shape
    x = h.reshape(b * s, D)
    idx, w = moe.route_sigmoid(
        x, lp["router"], lp["router_bias"], top_k=cfg.experts_per_tok,
        renorm=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor)
    y, sizes = moe.moe_ffn_dropless(bank, x, idx, w, layer=layer,
                                    valid=valid.reshape(b * s))
    with jax.named_scope(moe.SHARED_SCOPE):
        y = y + (jax.nn.silu(x @ lp["ws_gate"]) * (x @ lp["ws_up"])) \
            @ lp["ws_down"]
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()])
    return y.reshape(b, s, D), counts.astype(jnp.int32), idx


def _bank(params):
    return {n: params["moe"][n] for n in ("w_gate", "w_up", "w_down")}


def _without_bank(lp):
    return {n: a for n, a in lp.items()
            if n not in ("w_gate", "w_up", "w_down")}


def _scan_layers(cfg: LatentMoEConfig, params, x, state, attend, valid):
    """Every layer over x (b, s, dim): the leading dense layers, then
    the expert layers, each kind one ``lax.scan``, so the program does
    not grow with depth. ``attend(lp, layer, h, state) -> (o, state)``
    is the layer's attention on the normed input (``state`` is what it
    carries whole from layer to layer: the pool or the stage);
    ``valid`` (b, s) the real tokens. The expert bank goes into the
    loop whole and is reached by the layer's index, as the pool is: cut
    into a layer's slab by the scan it is copied, 600 MB a layer.
    Returns (x, state, counts (3,) summed over the expert layers, and
    for the checks the routers' picks (expert layers, b s, top_k) and
    the stream entering every layer (L, b, s, dim))."""
    bank = _bank(params)

    def attn(x, lp, layer, state):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        o, state = attend(lp, layer, h, state)
        x = x + _out_proj(cfg, lp, o)
        return x, rms_norm(x, lp["ffn_norm"], cfg.norm_eps), state

    def dense(carry, xs):
        x0, state = carry
        lp, layer = xs
        x, h, state = attn(x0, lp, layer, state)
        return (x + _dense_ffn(lp, h), state), x0

    def expert(carry, xs):
        x0, state, counts = carry
        lp, at = xs
        x, h, state = attn(x0, lp, cfg.first_k_dense + at, state)
        delta, c, picks = _moe_ffn(cfg, lp, bank, at, h, valid)
        return (x + delta, state, counts + c), (picks, x0)

    nd, nm = cfg.first_k_dense, cfg.n_moe_layers
    (x, state), ins_d = lax.scan(
        dense, (x, state),
        (params["dense"], jnp.arange(nd, dtype=jnp.int32)))
    (x, state, counts), (picks, ins_m) = lax.scan(
        expert, (x, state, jnp.zeros((3,), jnp.int32)),
        (_without_bank(params["moe"]), jnp.arange(nm, dtype=jnp.int32)))
    # what only a check reads (a program that does not is compiled
    # without them): the routers' picks and every layer's input stream
    return x, state, counts, {"picks": picks,
                              "streams": jnp.concatenate([ins_d, ins_m])}



# ---------------------------------------------------------------------------
# whole sequences: forward and the prefills (decompressed attention)
# ---------------------------------------------------------------------------
def _sequence_layers(cfg: LatentMoEConfig, params, tokens, start, rows,
                     n_valid):
    """Every layer over tokens (b, s) at positions ``start ..`` (a
    traced scalar): each layer writes the tokens' cache rows into
    ``rows`` (L, b, capacity, row_stored) at ``start`` and attends, in the
    decompressed form and causally by absolute position, over its rows
    ``[0, start + s)``; whatever ``rows`` holds from there on is not
    read. Tokens from ``n_valid`` on are padding. Returns (x (b, s,
    dim), rows, what ``_scan_layers`` saw for the checks)."""
    b, s = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    pos = start + jnp.arange(s, dtype=jnp.int32)
    cos, sin = _rope_tables(cfg, jnp.broadcast_to(pos, (b, s)))
    valid = jnp.broadcast_to(jnp.arange(s) < n_valid, (b, s))
    z = jnp.zeros((), jnp.int32)

    def attend(lp, layer, h, rows):
        q_nope, q_rope, new = _latent_qkv(cfg, lp, h, cos, sin)
        with jax.named_scope(KV_WRITE_SCOPE):
            rows = lax.dynamic_update_slice(
                rows, new[None].astype(rows.dtype), (layer, z, start, z))
        with jax.named_scope(MLA_SCOPE):
            o = latent_prefill_attention(
                q_nope, q_rope, rows, _wkvb(cfg, lp), layer=layer,
                q_offset=start, scale=cfg.scale,
                kv_block=kv_block(s))
        return o.astype(cfg.dtype), rows

    x, rows, _, seen = _scan_layers(
        cfg, params, llama._embed(cfg, params, tokens), rows, attend, valid)
    return x, rows, seen


def _fresh_rows(cfg: LatentMoEConfig, b: int, s: int):
    """An empty row store for a sequence of s tokens from position 0,
    its capacity a whole number of key blocks."""
    blk = kv_block(s)
    return jnp.zeros((cfg.n_layers, b, -(-s // blk) * blk,
                      cfg.row_stored), cfg.dtype)


def forward(cfg: LatentMoEConfig, params, tokens):
    """tokens (b, s) -> logits (b, s, V) float32: every layer on every
    position, no cache."""
    b, s = tokens.shape
    x, _, _ = _sequence_layers(cfg, params, tokens, 0,
                               _fresh_rows(cfg, b, s), s)
    return final_logits(cfg, params, x)


def router_picks(cfg: LatentMoEConfig, params, tokens):
    """The experts every expert layer's router chose for tokens (b, s)
    in a pass like :func:`forward`'s: (expert layers, b s, top_k). What
    a check counts near-tie flips against a float32 reference with."""
    b, s = tokens.shape
    return _sequence_layers(cfg, params, tokens, 0,
                            _fresh_rows(cfg, b, s), s)[2]["picks"]


def layer_streams(cfg: LatentMoEConfig, params, tokens):
    """The residual stream entering every layer, and leaving the last,
    in a pass like :func:`forward`'s: (L + 1, b, s, dim). What a check
    holds each layer's own arithmetic against a reference with, one
    layer at a time, so that a near-tie the router decides otherwise is
    one token's difference in one layer and not every later layer's."""
    b, s = tokens.shape
    x, _, seen = _sequence_layers(cfg, params, tokens, 0,
                                  _fresh_rows(cfg, b, s), s)
    return jnp.concatenate([seen["streams"], x[None]])


# ---------------------------------------------------------------------------
# serving state and programs
# ---------------------------------------------------------------------------
def decode_attention_path(cfg, kv, mesh=None, *, verify: bool = False) -> str:
    """Which attention the decode program is built on over the pool
    ``kv`` (arrays or shapes): ``ops.attention.latent_decode_path``'s
    answer for what :func:`decode_slots_paged` hands it."""
    del verify
    return latent_decode_path(
        (1, cfg.n_heads, 1, cfg.row_stored), kv["latent"].shape,
        kv["latent"].dtype, mesh=mesh)


def init_paged_cache(cfg: LatentMoEConfig, max_slots: int, n_pages: int,
                     page_size: int, mesh=None, int8: bool = False):
    """Device state for the paged serving engine: ``latent``, the page
    pool of cache rows, (L, n_pages, page_size, row_stored), token-major
    (``llama.init_paged_cache``'s layout: layer, page and in-page offset
    lead, which is what the decode write indexes) with ONE row a token
    and layer, ``[c; rotated k_rope; zeros to whole lane tiles]``
    (``LatentMoEConfig.row_stored`` says why), read by every head; plus the
    slot vectors (``serving.slot_vectors``). Page tables stay on the
    host."""
    serving.refuse("latent_moe", SERVE_UNSUPPORTED, mesh, int8)
    return {"latent": jnp.zeros((cfg.n_layers, n_pages, page_size,
                                 cfg.row_stored), cfg.dtype),
            **serving.slot_vectors(max_slots)}


copy_page = serving.copy_page


def decode_logits(cfg: LatentMoEConfig, params, kv, sv, active,
                  page_table):
    """The decode step up to its logits: (logits (S, V) float32, the
    pool with the step's rows written, counts (3,) int32 over the
    active slots' tokens: assignments, experts touched, the busiest
    experts' loads, each summed over the expert layers)."""
    pool = kv["latent"]
    ps = pool.shape[2]
    cap = page_table.shape[1] * ps
    pos = jnp.minimum(sv["lengths"].astype(jnp.int32), cap - 1)
    nslots = page_table.shape[0]
    phys = page_table[jnp.arange(nslots), pos // ps]
    off = pos % ps
    cos, sin = _rope_tables(cfg, pos[:, None])
    nope = cfg.qk_nope_head_dim

    def attend(lp, layer, h, pool):
        q_nope, q_rope, new = _latent_qkv(cfg, lp, h, cos, sin)
        with jax.named_scope(KV_WRITE_SCOPE):
            pool = pool.at[layer, phys, off].set(
                new[:, 0].astype(pool.dtype))
        wkvb = _wkvb(cfg, lp)
        with jax.named_scope(MLA_SCOPE):
            # a head's query in the latent space, beside its rope part:
            # one query of row_dim against the row
            q_lat = jnp.einsum("bhsn,rhn->bhsr", q_nope, wkvb[..., :nope])
            q = jnp.concatenate([q_lat.astype(cfg.dtype), q_rope], -1)
        o_lat = paged_latent_decode_attention(
            q, pool, page_table, pos + 1, layer=layer,
            value_dim=cfg.kv_lora_rank, scale=cfg.scale)
        with jax.named_scope(MLA_SCOPE):
            o = jnp.einsum("bhsr,rhv->bhsv", o_lat.astype(cfg.dtype),
                           wkvb[..., nope:])
        return o, pool

    x = llama._embed(cfg, params, sv["tokens"][:, None])
    x, pool, counts, _ = _scan_layers(cfg, params, x, pool, attend,
                                      active[:, None])
    return final_logits(cfg, params, x)[:, 0], pool, counts


def decode_slots_paged(cfg: LatentMoEConfig, params, kv, sv, active,
                       page_table, temperature, top_k, top_p, mesh=None):
    """ONE decode step over the bank: ``llama.decode_slots_paged``'s
    contract (same arguments, same sampling and rng chains), the
    attention in the absorbed form over the slots' pages. A slot that
    is not ``active`` flows through (fixed shape), writes to scratch
    page 0 and is routed to no expert. Returns (sampled tokens (S,)
    with ``STEP_COUNTS``' values behind them, (S + 3,) int32; new kv;
    new sv)."""
    del mesh
    logits, pool, counts = decode_logits(cfg, params, kv, sv, active,
                                         page_table)
    return serving.decode_step(logits, {"latent": pool}, sv, active,
                               temperature, top_k, top_p, counts)


# -- admission: the run, and the first-token seat every token family shares -
def _run(cfg: LatentMoEConfig, params, tokens, start, n_valid, stage, mesh):
    """``serving``'s run: every layer over the tokens, its rows into the
    stage's store; the head reads the last valid position alone."""
    del mesh
    rows = (_fresh_rows(cfg, *tokens.shape) if stage is None
            else stage["latent"])
    x, rows, _ = _sequence_layers(cfg, params, tokens, start, rows, n_valid)
    return serving.last_logits(final_logits, cfg, params, x, n_valid), \
        {"latent": rows}


_end = partial(serving._seat_first, serving.seat_pools, vectors_first=True)


def prefill_slot_paged(cfg: LatentMoEConfig, params, tokens, true_len,
                       prefix_len, pages_row, slot, kv, sv, rng,
                       temperature, top_k, top_p, mesh=None):
    """Admission of a whole prompt (``serving.prefill_whole``)."""
    return serving.prefill_whole(
        _run, _end, cfg, params, tokens, true_len, prefix_len, pages_row,
        slot, kv, sv, rng, temperature, top_k, top_p, mesh)


# -- a prompt in chunks: the stall a running request sees is one chunk's ----
def init_prefill_stage(cfg: LatentMoEConfig, capacity: int, chunk: int):
    """Where a prompt that is prefilled ``chunk`` tokens at a time keeps
    its cache rows until its last chunk seats them, outside the pool (a
    decode step in between runs over every slot's pages): (L, 1,
    capacity, row_stored)."""
    if capacity % chunk or chunk % kv_block(chunk):
        raise ValueError(
            f"a prefill chunk ({chunk}) divides the slot's capacity "
            f"({capacity}) and is a whole number of key blocks "
            f"({kv_block(chunk)})")
    return {"latent": jnp.zeros(
        (cfg.n_layers, 1, capacity, cfg.row_stored), cfg.dtype)}


def prefill_slot_paged_chunk(cfg: LatentMoEConfig, params, tokens, start,
                             stage, mesh=None):
    """A chunk that is not the prompt's last (``serving.prefill_chunk``)."""
    return serving.prefill_chunk(_run, cfg, params, tokens, start, stage,
                                 mesh)


def prefill_slot_paged_last(cfg: LatentMoEConfig, params, tokens, start,
                            n_valid, stage, pages_row, slot, kv, sv, rng,
                            temperature, top_k, top_p, mesh=None):
    """A prompt's last chunk and its seat (``serving.prefill_last``)."""
    return serving.prefill_last(
        _run, _end, cfg, params, tokens, start, n_valid, stage, pages_row,
        slot, kv, sv, rng, temperature, top_k, top_p, mesh)
