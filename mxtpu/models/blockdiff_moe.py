"""A decoder of Qwen3-MoE layers that generates by DIFFUSION OVER BLOCKS,
as SDAR wrote it down (JetLM, "SDAR: Synergistic Diffusion-
AutoRegression", 2025-10, and the release's ``generate.py``;
``model_type: sdar_moe``).

*The layer* is Qwen3-MoE's, key for key: ``x += W_o attn(RMSNorm(x))``
with grouped-query attention (``n_heads`` query heads over
``n_kv_heads`` KV heads of ``head_dim``), a head-wise RMSNorm on q and on
k before RoPE, no bias; then ``x += sum_k w_k E_k(RMSNorm(x))``: a
softmax router over ``n_experts`` SwiGLU experts, the top
``experts_per_tok`` renormalised (``parallel.moe.route_softmax``), the
dropless dispatch latent_moe runs (``parallel.moe.moe_ffn_dropless``),
no shared expert, no dense layer, an untied head.

*What SDAR changes* is the mask and the decoding loop. With block length
``B``, position i sees position j iff ``j // B <= i // B``: causal
across blocks, bidirectional inside one, prompt and answer alike; the
logits at position i predict the token AT i (no shift). A block of the
answer starts as ``B`` ``[MASK]``s and is denoised: a *denoise pass* runs
the block's ``B`` positions (tokens or ``[MASK]``) against the stored
keys and values and each other, draws a candidate at every masked
position and keeps the ``B / denoising_steps`` most confident
(``low_confidence_static``; ``low_confidence_dynamic`` keeps every one
over ``confidence_threshold`` if that is more); when no mask is left a
*commit pass* runs the same forward over the final tokens, whose keys and
values stay, and the block's new tokens are the request's next tokens.
So a pass yields NO token or a whole block, and a block of ``B`` tokens
costs ``denoising_steps + 1`` forward passes.

The serving surface is latent_moe's (llama's plus the chunk surface),
with :func:`block_step_slots_paged` in ``decode_slots_paged``'s place:
ONE program for every slot whatever its phase. It writes the block's
keys and values at ``length .. length + B - 1`` first (what lies past a
slot's length is excluded by every later read and overwritten in place,
as the speculative step's rejected drafts are: a denoise pass's write
needs no undo) and attends over ``length + B`` keys, so that no mask is
needed inside the block. The pools are sambay's: K and V each (L,
n_pages, page_size, n_kv_heads head_dim), a token's heads end to end in
whole lane tiles; the block's rows ride as further query heads of their
KV head through the walk over live pages that is there
(``ops.paged_attention.paged_attention_block``). The slot variables
gain the block: its tokens, which of them are still masked, which are
new (a prompt's remainder opens the first block unmasked), the pass
index. A prefill stores the keys and values of a prompt's WHOLE blocks,
seats the remainder as the first block and returns no token.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (BLOCK_SCOPE, block_causal_rows_attention,
                             block_decode_path, paged_block_attention)
from ..parallel import moe
from . import latent_moe, llama, retention
from .llama import KV_WRITE_SCOPE, SAMPLER_SCOPE, rms_norm

__all__ = ["BlockDiffMoEConfig", "CONFIGS", "init_params", "forward",
           "layer_streams", "router_picks", "init_paged_cache",
           "prefill_slot_paged", "init_prefill_stage",
           "prefill_slot_paged_chunk", "prefill_slot_paged_last",
           "decode_logits", "block_step_slots_paged", "unmask", "copy_page",
           "decode_attention_path", "positions_written",
           "serve_gauges",
           "STEP_COUNTS", "SLOT_VARS"]

# the named scopes of this family's programs besides llama.py's (embed,
# norm, qkv_proj, rope, kv_write, kv_gather, out_proj, lm_head, sampler)
# and parallel/moe.py's (moe_router, moe_dispatch, moe_experts):
# ``block_attention`` (ops/attention.py: a step's attention, a block of
# rows a slot; a prefill's block-causal attention) and ``unmask`` (the
# confidence, the ranking, the transfer and the block's bookkeeping)
UNMASK_SCOPE = "unmask"

REMASKING = ("low_confidence_static", "low_confidence_dynamic")

# what ``ServeEngine`` cannot do for this family yet, by option, with
# the mechanism in the way (the engine raises with these words)
SERVE_UNSUPPORTED = {
    "prefix_cache": "a shared prefix must end on a block boundary (a "
                    "block's keys are computed seeing the whole block), "
                    "and the prefix cache registers whole prompts and "
                    "forks mid-page",
    "speculate_k": "a step already runs a block of positions a slot; a "
                   "draft to verify would be a second block behind a "
                   "block that is not final",
    "int8_pages": "a denoise pass overwrites the block's tentative keys "
                  "in place, and no quantised form of that write (a "
                  "scale a token, rewritten a pass) is written down",
    "submit_prefilled": "a disaggregated hand-off carries a first token "
                        "and per-head keys, and a block-diffusion prefill "
                        "yields no token and a block state",
    "mesh": "no sharding rules for the expert bank or the pools of "
            "token rows yet (a chip's share of the experts and the ep "
            "exchange)",
    "resume_key": "a pass splits the slot's chain once whatever it "
                  "emits, so the chain's state after n tokens depends "
                  "on the schedule, not on n alone",
}
# which kind of state each donated array is (the engine's byte gauges)
STATE_KINDS = {"k": "kv_pages", "v": "kv_pages"}
# the per-slot vectors the engine keeps beside the donated state: every
# family's three, and the block (``tokens`` is (slots, B) here)
SLOT_VARS = ("lengths", "tokens", "rngs", "masked", "fresh", "passes")
# what the step program counts on the device (``latent_moe.STEP_COUNTS``'
# contract: the values ride behind the tokens in the program's first
# output, in this order, as int32)
STEP_COUNTS = latent_moe.STEP_COUNTS + (
    {"name": "serve_block_passes_total",
     "help": "Forward passes of a block-diffusion step, a slot at a time "
             "(denoise and commit passes of the slots that ran)"},
    {"name": "serve_blocks_committed_total",
     "help": "Blocks committed (their keys and values kept, their new "
             "tokens emitted)"},
    {"name": "serve_block_unmasked_total",
     "help": "Masked positions that took their candidate in a denoise "
             "pass"},
    {"name": "serve_block_threshold_transfers_total",
     "help": "Of those, the ones taken because their confidence passed "
             "the threshold (low_confidence_dynamic)"},
)

_F32 = jnp.float32
_I32 = jnp.int32


@dataclass(frozen=True)
class BlockDiffMoEConfig:
    family: ClassVar[str] = "blockdiff_moe"
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_hidden_dim: int = 768
    n_experts: int = 128
    experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq_len: int = 32768
    tie_embeddings: bool = False
    # what the published config does not give (the benchmark's
    # configuration lists each under ``assumed``): the release's
    # defaults for the -Chat models
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669
    dtype: Any = jnp.bfloat16        # activations
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over "
                             f"{self.n_kv_heads} KV heads")
        if self.head_dim % 2:
            raise ValueError("RoPE pairs up head_dim's columns")
        if self.experts_per_tok > self.n_experts:
            raise ValueError("more experts a token than experts")
        if self.block_length % self.denoising_steps:
            raise ValueError(
                f"{self.denoising_steps} denoising steps do not divide a "
                f"block of {self.block_length}")
        if self.remasking not in REMASKING:
            raise ValueError(f"remasking {self.remasking!r}: one of "
                             f"{REMASKING}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id is a row of the vocabulary")

    @property
    def kv_width(self) -> int:       # a token's row in a pool, a layer
        return self.n_kv_heads * self.head_dim

    @property
    def per_pass(self) -> int:       # positions a denoise pass unmasks
        return self.block_length // self.denoising_steps

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)


CONFIGS = {
    # the published ratios at toy widths: 8 query heads over 2 KV heads
    # of 16, 16 experts top-4, a block of 4 in 4 steps, the mask id
    # inside the vocabulary
    "tiny": BlockDiffMoEConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=8, n_kv_heads=2,
        head_dim=16, moe_hidden_dim=48, n_experts=16, experts_per_tok=4,
        max_seq_len=256, mask_token_id=250, dtype=jnp.float32,
        param_dtype=jnp.float32),
    "sdar_30b_a3b": BlockDiffMoEConfig(),
}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: BlockDiffMoEConfig, rng: Optional[jax.Array] = None):
    """Random weights, scaled by fan-in (out-projections by fan-in x 2 x
    layers), norm weights 1; every layer alike, stacked on a leading
    axis, the expert bank three arrays of (layers, experts, ..) which
    the grouped product takes as they are stored."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 8)
    d, D, L = cfg.param_dtype, cfg.dim, cfg.n_layers
    H, W, hd = cfg.n_heads * cfg.head_dim, cfg.kv_width, cfg.head_dim
    normal = latent_moe._normal
    fan = 2 * L
    layers = {
        "attn_norm": jnp.ones((L, D), d), "ffn_norm": jnp.ones((L, D), d),
        "q_norm": jnp.ones((L, hd), d), "k_norm": jnp.ones((L, hd), d),
        "wq": normal(ks[0], (L, D, H), D, d),
        "wk": normal(ks[1], (L, D, W), D, d),
        "wv": normal(ks[2], (L, D, W), D, d),
        "wo": normal(ks[3], (L, H, D), H * fan, d),
        "router": normal(ks[4], (L, D, cfg.n_experts), D, d)}
    layers.update(latent_moe._init_swiglu(
        ks[5], D, cfg.moe_hidden_dim, cfg.moe_hidden_dim * fan, d,
        (L, cfg.n_experts)))
    return {"tok_embed": normal(ks[6], (cfg.vocab_size, D), D, d),
            "layers": layers, "final_norm": jnp.ones((D,), d),
            "lm_head": normal(ks[7], (D, cfg.vocab_size), D, d)}


# ---------------------------------------------------------------------------
# the pieces of a layer
# ---------------------------------------------------------------------------
# cos and sin of positions (b, s) -> (b, 1, s, head_dim / 2), as
# ``llama.apply_rope`` takes them beside (b, heads, s, head_dim)
_rope_tables = retention._rope_tables


def _project(cfg: BlockDiffMoEConfig, lp, h, cos, sin):
    """h (b, s, dim) -> q (b, H, s, hd), normed a head and rotated, and
    the tokens' rows as the pools store them, k (normed and rotated) and
    v, each (b, s, G hd)."""
    b, s, _ = h.shape
    hd = cfg.head_dim
    with jax.named_scope("qkv_proj"):
        heads = lambda w, n: (h @ w).reshape(b, s, n, hd).transpose(
            0, 2, 1, 3)
        q = heads(lp["wq"], cfg.n_heads)
        k = heads(lp["wk"], cfg.n_kv_heads)
        v = h @ lp["wv"]
    q = llama.apply_rope(rms_norm(q, lp["q_norm"], cfg.norm_eps), cos, sin)
    k = llama.apply_rope(rms_norm(k, lp["k_norm"], cfg.norm_eps), cos, sin)
    with jax.named_scope("rope"):
        k = k.transpose(0, 2, 1, 3).reshape(b, s, cfg.kv_width)
    return q, k, v


_BANK = ("w_gate", "w_up", "w_down")


def _scan_layers(cfg: BlockDiffMoEConfig, params, x, state, cos, sin,
                 attend, valid):
    """Every layer over x (b, s, dim), one ``lax.scan``, so the program
    does not grow with depth. ``attend(q, k, v, layer, state) -> (o (b,
    H, s, hd), state)`` is the layer's attention on its normed, rotated
    queries and the tokens' own rows; ``state`` is what it carries whole
    from layer to layer (the pools or the row stores); ``valid`` (b, s)
    the real tokens (the others are routed nowhere). The expert bank
    goes into the loop whole and is reached by the layer's index
    (``latent_moe._scan_layers`` says why). Returns (x, state, counts
    (3,) int32 summed over the layers: assignments, experts touched, the
    busiest experts' loads; what only a check reads: the routers' picks
    (L, b s, top_k) and the stream entering every layer (L, b, s,
    dim))."""
    layers = params["layers"]
    bank = {n: layers[n] for n in _BANK}
    b, s, D = x.shape

    def body(carry, xs):
        x0, state, counts = carry
        lp, layer = xs
        h = rms_norm(x0, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _project(cfg, lp, h, cos, sin)
        o, state = attend(q, k, v, layer, state)
        x = x0 + llama._out_proj(cfg, lp, o.astype(cfg.dtype))
        h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps).reshape(b * s, D)
        idx, w = moe.route_softmax(h, lp["router"],
                                   top_k=cfg.experts_per_tok,
                                   renorm=cfg.norm_topk_prob)
        y, sizes = moe.moe_ffn_dropless(bank, h, idx, w, layer=layer,
                                        valid=valid.reshape(b * s))
        c = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()])
        return (x + y.reshape(b, s, D), state, counts + c.astype(_I32)), \
            (idx, x0)

    (x, state, counts), (picks, streams) = lax.scan(
        body, (x, state, jnp.zeros((3,), _I32)),
        ({n: a for n, a in layers.items() if n not in _BANK},
         jnp.arange(cfg.n_layers, dtype=_I32)))
    return x, state, counts, {"picks": picks, "streams": streams}


def _final(cfg, params, x):
    return llama._lm_head(cfg, params, rms_norm(x, params["final_norm"],
                                                cfg.norm_eps))


# ---------------------------------------------------------------------------
# whole sequences: forward and the prefills (block-causal)
# ---------------------------------------------------------------------------
def _kv_block(s: int) -> int:
    """Keys a step of the prefill attention's loop reads, for a run of
    s queries; a row store's capacity is a multiple of it."""
    return min(512, s)


def _sequence_layers(cfg: BlockDiffMoEConfig, params, tokens, start, rows,
                     n_valid):
    """Every layer over tokens (b, s) at positions ``start ..`` (a
    traced scalar, a multiple of the block length): each layer writes
    the tokens' keys and values into the row stores ``rows`` = (k, v),
    each (L, b, capacity, G hd), at ``start`` and attends block-causally
    by absolute position over its rows below the end of each query's own
    block. Tokens from ``n_valid`` on are padding. Returns (x (b, s,
    dim), rows, what ``_scan_layers`` saw for the checks)."""
    b, s = tokens.shape
    start = jnp.asarray(start, _I32)
    pos = start + jnp.arange(s, dtype=_I32)
    cos, sin = _rope_tables(cfg, jnp.broadcast_to(pos, (b, s)))
    valid = jnp.broadcast_to(jnp.arange(s) < n_valid, (b, s))
    z = jnp.zeros((), _I32)

    def attend(q, k, v, layer, rows):
        with jax.named_scope(KV_WRITE_SCOPE):
            rows = tuple(lax.dynamic_update_slice(
                store, new[None].astype(store.dtype), (layer, z, start, z))
                for store, new in zip(rows, (k, v)))
        with jax.named_scope(BLOCK_SCOPE):
            o = block_causal_rows_attention(
                q, *rows, layer=layer, q_offset=start,
                block=cfg.block_length, scale=cfg.scale,
                kv_block=_kv_block(s))
        return o, rows

    x, rows, _, seen = _scan_layers(
        cfg, params, llama._embed(cfg, params, tokens), rows, cos, sin,
        attend, valid)
    return x, rows, seen


def _fresh_rows(cfg: BlockDiffMoEConfig, b: int, s: int):
    """Empty row stores for a sequence of s tokens from position 0,
    their capacity a whole number of key blocks."""
    blk = _kv_block(s)
    shape = (cfg.n_layers, b, -(-s // blk) * blk, cfg.kv_width)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def forward(cfg: BlockDiffMoEConfig, params, tokens):
    """tokens (b, s) -> logits (b, s, V) float32 under the block-causal
    mask: every layer on every position, no cache. The logits at
    position i are the prediction of the token AT i."""
    b, s = tokens.shape
    x, _, _ = _sequence_layers(cfg, params, tokens, 0,
                               _fresh_rows(cfg, b, s), s)
    return _final(cfg, params, x)


def router_picks(cfg: BlockDiffMoEConfig, params, tokens):
    """The experts every layer's router chose for tokens (b, s) in a
    pass like :func:`forward`'s: (L, b s, top_k)."""
    b, s = tokens.shape
    return _sequence_layers(cfg, params, tokens, 0,
                            _fresh_rows(cfg, b, s), s)[2]["picks"]


def layer_streams(cfg: BlockDiffMoEConfig, params, tokens):
    """The residual stream entering every layer, and leaving the last,
    in a pass like :func:`forward`'s: (L + 1, b, s, dim)
    (``latent_moe.layer_streams``' contract)."""
    b, s = tokens.shape
    x, _, seen = _sequence_layers(cfg, params, tokens, 0,
                                  _fresh_rows(cfg, b, s), s)
    return jnp.concatenate([seen["streams"], x[None]])


# ---------------------------------------------------------------------------
# serving state and programs
# ---------------------------------------------------------------------------
def positions_written(cfg: BlockDiffMoEConfig, prompt_len: int,
                      max_new_tokens: int) -> int:
    """How many positions of its pages a request can write: a block is
    written whole, so ``prompt + max_new_tokens`` rounded up to one."""
    B = cfg.block_length
    return -(-(prompt_len + max_new_tokens) // B) * B


def serve_gauges(cfg: BlockDiffMoEConfig):
    """What an engine of this family states once: (name, help, value)."""
    return (("serve_block_length", "Positions of a block of diffusion: "
             "the most tokens a slot's step yields", cfg.block_length),
            ("serve_denoising_steps", "Denoise passes a full block takes "
             "before its commit pass", cfg.denoising_steps))


def decode_attention_path(cfg, kv, mesh=None, *, verify: bool = False) -> str:
    """Which attention :func:`block_step_slots_paged` is built on over
    the pools ``kv`` (arrays or shapes): ``ops.attention.
    block_decode_path``'s answer for a block of ``block_length`` query
    rows a slot. ``"pages"``: the walk over live pages, nothing
    gathered; ``"gathered"``: every slot's whole row of pages copied
    out. Static per compiled program."""
    del verify
    return block_decode_path(
        (1, cfg.n_heads, cfg.block_length, cfg.head_dim), kv["k"].shape,
        kv["k"].dtype, mesh=mesh)


def init_paged_cache(cfg: BlockDiffMoEConfig, max_slots: int, n_pages: int,
                     page_size: int, mesh=None, int8: bool = False):
    """Device state for the paged serving engine: ``k``/``v`` the page
    pools, each (L, n_pages, page_size, n_kv_heads head_dim), token-major
    (``llama.init_paged_cache``'s layout: layer, page and in-page offset
    lead, which is what the step's write indexes) with a token's heads
    END TO END: four heads of 128 laid out as (.., 4, 128) are half an
    (8, 128) tile a token, which the chip pads or relays out around
    every write (``sambay.init_paged_cache`` met the same). Plus the
    per-slot vectors (``SLOT_VARS``): ``lengths`` (positions whose keys
    are final), ``rngs``, and the block: ``tokens`` (slots, B), ``masked``
    (slots, B) which of them are still ``[MASK]``, ``fresh`` (slots, B)
    which are the request's new tokens (a prompt's remainder is not),
    ``passes`` the pass index inside the block. Page tables stay on the
    host."""
    if mesh is not None or int8:
        raise ValueError("blockdiff_moe: " + SERVE_UNSUPPORTED[
            "mesh" if mesh is not None else "int8_pages"])
    if page_size % cfg.block_length:
        raise ValueError(
            f"a block of {cfg.block_length} must not straddle a page of "
            f"{page_size}")
    pool = (cfg.n_layers, n_pages, page_size, cfg.kv_width)
    B = cfg.block_length
    return {
        "k": jnp.zeros(pool, cfg.dtype), "v": jnp.zeros(pool, cfg.dtype),
        "lengths": jnp.zeros((max_slots,), _I32),
        "tokens": jnp.zeros((max_slots, B), _I32),
        "rngs": jnp.zeros((max_slots, 2), jnp.uint32),
        "masked": jnp.ones((max_slots, B), bool),
        "fresh": jnp.ones((max_slots, B), bool),
        "passes": jnp.zeros((max_slots,), _I32)}


copy_page = llama.copy_page


def decode_logits(cfg: BlockDiffMoEConfig, params, kv, sv, active,
                  page_table):
    """A step up to its logits: every slot's block (its tokens, ``[MASK]``
    where ``masked``) at positions ``length .. length + B - 1``, its keys
    and values written there first, each row attending over ``length +
    B`` keys. Returns (logits (S, B, V) float32, the pools with the
    blocks written, counts (3,) int32 over the active slots' rows). A
    slot that is not ``active`` flows through (fixed shape), writes to
    scratch page 0, attends nothing and is routed to no expert."""
    ck, cv = kv["k"], kv["v"]
    ps, B = ck.shape[2], cfg.block_length
    nslots = page_table.shape[0]
    cap = page_table.shape[1] * ps
    at = jnp.arange(B, dtype=_I32)
    base = jnp.minimum(sv["lengths"].astype(_I32), cap - B)
    pos = base[:, None] + at                              # (S, B)
    # a block never straddles a page: one page a slot
    phys = jnp.broadcast_to(
        page_table[jnp.arange(nslots), base // ps][:, None], pos.shape)
    off = pos % ps
    seen = jnp.where(active, base + B, 0)
    cos, sin = _rope_tables(cfg, pos)

    def attend(q, k, v, layer, pools):
        pools = llama._write_pages(*pools, k, v, layer, phys, off)
        return paged_block_attention(q, *pools, page_table, seen,
                                     layer=layer, scale=cfg.scale), pools

    toks = jnp.where(sv["masked"], _I32(cfg.mask_token_id), sv["tokens"])
    x, (ck, cv), counts, _ = _scan_layers(
        cfg, params, llama._embed(cfg, params, toks), (ck, cv), cos, sin,
        attend, jnp.broadcast_to(active[:, None], pos.shape))
    return _final(cfg, params, x), {"k": ck, "v": cv}, counts


def unmask(cfg: BlockDiffMoEConfig, logits, masked, keys, temperature,
           top_k, top_p):
    """One denoise pass's decision on logits (S, B, V) float32: at every
    position draw a candidate ``x0`` (``llama.sample_logits``'s
    thresholds and draw, the mask id's logit at -inf first; row b of
    slot s from ``split(keys[s], B)[b]``) and take its confidence, the
    probability of ``x0`` under the distribution it was drawn from (a
    greedy row: the argmax and its softmax probability at temperature
    1); then the transfer: of the ``masked`` (S, B) positions the
    ``per_pass`` most confident (``low_confidence_static``; ties to the
    lower position), or every one whose confidence passes
    ``confidence_threshold`` if those are no fewer
    (``low_confidence_dynamic``). Confidence and ranking stay float32.
    Returns (x0 (S, B) int32, confidence (S, B) float32, take (S, B)
    bool, by_threshold (S,) bool: the slots whose transfer was the
    threshold's)."""
    S, B, V = logits.shape
    rows = lambda a: jnp.repeat(jnp.asarray(a), B)
    with jax.named_scope(SAMPLER_SCOPE):
        # a select, not a scatter: it fuses into the rows' first reader
        lg = jnp.where(jnp.arange(V) == cfg.mask_token_id, -jnp.inf,
                       logits.reshape(S * B, V))
        greedy, cut = llama._masked_logits(
            lg, rows(temperature), rows(top_k), rows(top_p))
        sub = jax.vmap(lambda k: jax.random.split(k, B))(keys)
        x0 = jax.vmap(llama._draw)(sub.reshape(S * B, 2), greedy, cut)
    with jax.named_scope(UNMASK_SCOPE):
        src = jnp.where((greedy < 0)[:, None], cut, lg)
        took = jnp.take_along_axis(src, x0[:, None], axis=-1)[:, 0]
        conf = jnp.exp(took - jax.nn.logsumexp(src, axis=-1))
        conf = conf.astype(_F32).reshape(S, B)
        x0 = x0.reshape(S, B)
        c = jnp.where(masked, conf, -jnp.inf)
        # position i's rank among the slot's: how many come before it
        ahead = (c[:, None, :] > c[:, :, None]) | (
            (c[:, None, :] == c[:, :, None])
            & (jnp.arange(B)[None, None, :] < jnp.arange(B)[None, :, None]))
        take = masked & (ahead.sum(-1) < cfg.per_pass)
        by_threshold = jnp.zeros((S,), bool)
        if cfg.remasking == "low_confidence_dynamic":
            passing = masked & (conf > cfg.confidence_threshold)
            by_threshold = passing.sum(-1) >= cfg.per_pass
            take = jnp.where(by_threshold[:, None], passing, take)
    return x0, conf, take, by_threshold


def block_step_slots_paged(cfg: BlockDiffMoEConfig, params, kv, sv, active,
                           page_table, temperature, top_k, top_p, mesh=None):
    """ONE pass over the bank, every slot whatever its phase
    (``llama.decode_slots_paged``'s arguments). A slot whose block still
    has a mask makes a DENOISE pass: the masked positions chosen by
    :func:`unmask` take their candidates, nothing is emitted, the length
    stays (the keys and values this pass wrote are tentative: the next
    pass overwrites them in place). A slot with no mask left makes its
    COMMIT pass: the keys and values just written are the final tokens'
    and stay, the length advances by ``B``, the block's ``fresh`` tokens
    are emitted and the next block opens, all ``[MASK]``. Every pass
    splits the slot's chain once.

    Returns (out (2 S B + 7,) int32, new kv, new sv), as
    ``llama.decode_slots_paged`` does; ``out`` holds the blocks' tokens
    row-major, then for each of them 1 where it is the request's next
    token (a commit pass's ``fresh`` positions, in order: a slot's length
    advances by ``B`` in the pass that emits, and the tokens it emits
    with the prompt's remainder are those ``B``), then ``STEP_COUNTS``'
    values."""
    del mesh
    B = cfg.block_length
    logits, pools, counts = decode_logits(cfg, params, kv, sv, active,
                                          page_table)
    with jax.named_scope(SAMPLER_SCOPE):
        keys = jax.vmap(jax.random.split)(sv["rngs"])
    masked = sv["masked"]
    x0, _, take, by_threshold = unmask(cfg, logits, masked, keys[:, 1],
                                       temperature, top_k, top_p)
    with jax.named_scope(UNMASK_SCOPE):
        commit = ~masked.any(-1)
        tokens = jnp.where(take, x0, sv["tokens"])
        emit = (commit & active)[:, None] & sv["fresh"]
        grew = jnp.where(commit & active, _I32(B), _I32(0))
        took = (take & active[:, None]).sum(-1, dtype=_I32)
        share = (counts[2].astype(_F32) * 1e6
                 / jnp.maximum(counts[0], 1).astype(_F32)).astype(_I32)
        out = jnp.concatenate([
            tokens.reshape(-1).astype(_I32), emit.reshape(-1).astype(_I32),
            counts[:2], share[None],
            jnp.stack([active.sum(dtype=_I32),
                       (commit & active).sum(dtype=_I32),
                       took.sum(), jnp.where(by_threshold, took, 0).sum()]
                      ).astype(_I32)])
        opened = commit[:, None]

        def ran_only(new, old):     # a slot that did not run keeps its own
            return jnp.where(active.reshape((-1,) + (1,) * (old.ndim - 1)),
                             new.astype(old.dtype), old)
        new_sv = {
            "lengths": sv["lengths"].astype(_I32) + grew,
            "tokens": ran_only(tokens, sv["tokens"]),
            "rngs": ran_only(keys[:, 0], sv["rngs"]),
            "masked": ran_only(jnp.where(opened, True, masked & ~take),
                               masked),
            "fresh": ran_only(jnp.where(opened, True, sv["fresh"]),
                              sv["fresh"]),
            "passes": ran_only(jnp.where(commit, 0, sv["passes"] + 1),
                               sv["passes"])}
    return out, pools, new_sv


@jax.named_scope(KV_WRITE_SCOPE)
def _seat_rows(kv, rows, pages_row):
    """A prefilled prompt's rows (k, v), each (L, 1, capacity, G hd),
    into the slot's pages; entries of ``pages_row`` past the granted
    ones name scratch page 0, which is never attended."""
    out = {}
    for name, store in zip(("k", "v"), rows):
        pool = kv[name]
        ps = pool.shape[2]
        store = store[:, 0]
        store = jnp.pad(store, ((0, 0), (0, -store.shape[1] % ps), (0, 0)))
        pages = store.reshape(store.shape[0], -1, ps, store.shape[-1])
        n = min(pages.shape[1], pages_row.shape[0])
        out[name] = pool.at[:, pages_row[:n]].set(
            pages[:, :n].astype(pool.dtype))
    return out


def _seat_block(cfg, tokens, start, n_valid, rows, pages_row, slot, kv, sv,
                rng):
    """The end of an admission: the prompt's whole blocks are in
    ``rows``; they are seated into the slot's pages, the slot's length
    is their count, and the prompt's remainder (``(start + n_valid) %
    B`` tokens) opens the first block unmasked beside ``[MASK]``s. No
    head runs and NO token comes out: the first tokens are the first
    block's, passes later. Returns (no tokens (0,) int32, new kv, new
    sv)."""
    B = cfg.block_length
    total = start + n_valid
    whole = total // B * B
    left = total - whole
    tail = lax.dynamic_slice_in_dim(tokens[0], whole - start, B)
    here = jnp.arange(B) >= left
    z = jnp.zeros((), _I32)

    def put(name, value):
        return lax.dynamic_update_slice(
            sv[name], jnp.asarray(value, sv[name].dtype)[None],
            (slot,) + (z,) * (sv[name].ndim - 1))
    new_sv = {
        "lengths": put("lengths", whole),
        "tokens": put("tokens", jnp.where(here, cfg.mask_token_id, tail)),
        "rngs": put("rngs", rng), "masked": put("masked", here),
        "fresh": put("fresh", here), "passes": put("passes", z)}
    return jnp.zeros((0,), _I32), _seat_rows(kv, rows, pages_row), new_sv


def _whole_blocks(cfg, start, n_valid):
    """Of a run of ``n_valid`` tokens from ``start``, how many lie in
    the prompt's whole blocks: their keys are final and are stored; the
    remainder's are computed again with its block."""
    B = cfg.block_length
    return (start + n_valid) // B * B - start


def prefill_slot_paged(cfg: BlockDiffMoEConfig, params, tokens, true_len,
                       prefix_len, pages_row, slot, kv, sv, rng,
                       temperature, top_k, top_p, mesh=None):
    """Admission: ``llama.prefill_slot_paged``'s arguments, cold only
    (the engine refuses a prefix cache for this family). Every layer
    over the prompt's whole blocks under the block-causal mask (the
    prompt END-padded to its bucket; the remainder and the padding are
    routed nowhere and their rows never attended), no head. Returns (no
    tokens (0,), new kv, new sv)."""
    del prefix_len, temperature, top_k, top_p, mesh
    true_len = jnp.asarray(true_len, _I32)
    z = jnp.zeros((), _I32)
    _, rows, _ = _sequence_layers(
        cfg, params, tokens, 0, _fresh_rows(cfg, *tokens.shape),
        _whole_blocks(cfg, z, true_len))
    return _seat_block(cfg, tokens, z, true_len, rows, pages_row,
                       jnp.asarray(slot, _I32), kv, sv, rng)


# -- a prompt in chunks: the stall a running request sees is one chunk's ----
def init_prefill_stage(cfg: BlockDiffMoEConfig, capacity: int, chunk: int):
    """Where a prompt that is prefilled ``chunk`` tokens at a time keeps
    its keys and values until its last chunk seats them, outside the
    pools (a step in between runs over every slot's pages): ``k``/``v``
    (L, 1, capacity rounded up to whole chunks, G hd)."""
    if chunk % cfg.block_length or chunk % _kv_block(chunk):
        raise ValueError(
            f"a prefill chunk ({chunk}) is a whole number of blocks "
            f"({cfg.block_length}) and of key blocks ({_kv_block(chunk)})")
    shape = (cfg.n_layers, 1, -(-capacity // chunk) * chunk, cfg.kv_width)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def prefill_slot_paged_chunk(cfg: BlockDiffMoEConfig, params, tokens, start,
                             stage, mesh=None):
    """One whole chunk of a prompt that is not its last: every layer
    over tokens (1, chunk) at positions ``start ..``, each attending
    block-causally to the rows the chunks before left in the stage and
    to its own. The pools are not touched."""
    del mesh
    _, (k, v), _ = _sequence_layers(cfg, params, tokens, start,
                                    (stage["k"], stage["v"]),
                                    tokens.shape[1])
    return {"k": k, "v": v}


def prefill_slot_paged_last(cfg: BlockDiffMoEConfig, params, tokens, start,
                            n_valid, stage, pages_row, slot, kv, sv, rng,
                            temperature, top_k, top_p, mesh=None):
    """A prompt's last chunk, ``n_valid`` tokens END-padded to tokens
    (1, chunk), at positions ``start ..``; then the admission's end as
    ``prefill_slot_paged``'s: the whole prompt's whole blocks seated
    into the slot's pages, the remainder seated as the first block.
    Returns (no tokens (0,), new kv, new sv)."""
    del temperature, top_k, top_p, mesh
    start = jnp.asarray(start, _I32)
    n_valid = jnp.asarray(n_valid, _I32)
    _, rows, _ = _sequence_layers(
        cfg, params, tokens, start, (stage["k"], stage["v"]),
        _whole_blocks(cfg, start, n_valid))
    return _seat_block(cfg, tokens, start, n_valid, rows, pages_row,
                       jnp.asarray(slot, _I32), kv, sv, rng)
