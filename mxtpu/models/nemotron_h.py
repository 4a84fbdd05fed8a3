"""Nemotron-H: a hybrid decoder of Mamba-2 mixers, routed experts and a
few attention layers (NVIDIA Nemotron-3-Nano-30B-A3B, ``model_type:
nemotron_h``). Block ``i`` is chosen by character ``i`` of
``hybrid_override_pattern`` (``M`` Mamba-2, ``E`` experts, ``*``
attention), and every block is pre-norm residual, ``h = h +
mixer(RMSNorm(h))`` (eps ``norm_eps``); then the final RMSNorm and an
untied head.

*M, Mamba-2* (``transformers``' ``Mamba2Mixer.torch_forward``):

1. ``in_proj`` gives ``[z (H p) | xBC (H p + 2 G n) | dt (H)]`` with H
   ``mamba_heads`` of ``mamba_head_dim`` p channels, G ``n_groups`` and
   n ``d_state``;
2. ``xBC = silu(causal_conv1d(xBC, d_conv taps, bias))``, split into
   ``x`` (H heads of p), ``B`` and ``C`` (G groups of n each); head h
   reads group ``h // (H / G)``;
3. ``dt = softplus(dt + dt_bias)`` (no clamp: the published
   ``time_step_limit`` is (0, inf)); ``A = -exp(A_log)``, a scalar a
   head;
4. ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``, ``y_t = S_t C_t
   + D x_t`` (``ops.ssm``: ``ssd_step`` a token, ``ssd_chunked`` a
   sequence in chunks of ``ssd_chunk``);
5. ``y = GroupRMSNorm(y * silu(z)) w``, each group of ``H p / G``
   channels normalised on its own, after the gate;
6. ``out_proj``.

*E, experts*: the bias-corrected sigmoid router of one group
(``parallel.moe.route_sigmoid``, ``experts_per_tok`` of
``n_routed_experts``, renormalised, times ``routed_scaling_factor``)
over NON-gated experts, ``sum_k w_k down_k(relu(up_k x)^2)``
(``parallel.moe.moe_ffn_dropless`` over a bank with no ``w_gate``), plus
one shared expert ``down_s(relu(up_s x)^2)``.

*\\*, attention*: ``n_heads`` query heads over ``n_kv_heads`` KV heads of
``head_dim``, no bias, softmax scale ``1 / sqrt(head_dim)``, and NO
position encoding (the published code applies none; the Mamba-2 layers
carry order).

Serving keeps two kinds of state side by side (``init_paged_cache``):
the attention layers' keys and values in a page pool (the only state
that grows with the sequence: a token's KV heads end to end, 256 values
at the published widths, whole lane tiles, read in place by
``ops.paged_attention.paged_attention_rows`` on a TPU), and a fixed
convolution tail and float32 SSM state a Mamba layer and slot, read and
written in place by the decode step. The serving surface is
``llama.py``'s (``init_params``, ``forward``, ``init_paged_cache``,
``decode_attention_path``, ``decode_slots_paged``,
``prefill_slot_paged``, ``copy_page``) and sambay's chunk surface
(``init_prefill_stage``, ``prefill_slot_paged_chunk``, ``.._last``): a
chunk's keys and values, convolution tails and states wait in the stage,
and the last chunk seats them into the slot. The decode program counts
what the router did, as ``latent_moe``'s does (``STEP_COUNTS``). The
layers are unrolled by the pattern: its kinds do not repeat in a period
a scan could take.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (ATTENTION_SCOPE, block_causal_rows_attention,
                             gathered_rows_decode_attention, rows_decode_path)
from ..ops.ssm import causal_conv1d, ssd_chunked, ssd_step
from ..parallel import moe
from . import llama
from .latent_moe import STEP_COUNTS
from .llama import KV_WRITE_SCOPE, rms_norm

__all__ = ["NemotronHConfig", "CONFIGS", "init_params", "forward",
           "init_paged_cache", "prefill_slot_paged", "init_prefill_stage",
           "prefill_slot_paged_chunk", "prefill_slot_paged_last",
           "decode_slots_paged", "copy_page", "decode_attention_path",
           "router_picks", "layer_streams", "ssm_inputs", "STEP_COUNTS"]

# the named scopes of this family's programs besides the ones it shares
# (embed, norm, qkv_proj, out_proj, kv_write, kv_gather, attention,
# lm_head, sampler, the four ``moe_*``): ``ssm``, the whole Mamba-2
# mixer, opened at the top level of its layer; and inside it
# ``ssm_state``, the recurrence alone (the decode step's state update,
# the chunk's SSD), which a trace reader finds as ``ssm/ssm_state``
SSM_SCOPE = "ssm"
SSM_STATE_SCOPE = "ssm_state"

# what ``ServeEngine`` cannot do for this family yet, by option, with
# the mechanism in the way (the engine raises with these words)
SERVE_UNSUPPORTED = {
    "prefix_cache": "a prefix hit would need a snapshot of the Mamba-2 "
                    "state and convolution tails at the shared boundary, "
                    "and pages hold keys and values only",
    "speculate_k": "a rejected draft would need the Mamba-2 state rolled "
                   "back to the last accepted token",
    "int8_pages": "no quantised form of the attention pool is written "
                  "down yet",
    "submit_prefilled": "a disaggregated hand-off carries keys and values"
                        " only, not the Mamba-2 state",
    "mesh": "no sharding rules for the expert bank or the Mamba-2 state "
            "yet (a chip's share of the experts and the ep exchange)",
}
# which kind of state each donated array is (the engine's byte gauges)
STATE_KINDS = {"k": "kv_pages", "v": "kv_pages", "conv": "ssm",
               "ssm": "ssm"}

_F32 = jnp.float32


@dataclass(frozen=True)
class NemotronHConfig:
    family: ClassVar[str] = "nemotron_h"
    vocab_size: int = 131072
    dim: int = 2688
    pattern: str = ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    d_state: int = 128
    d_conv: int = 4
    ssd_chunk: int = 128
    moe_hidden_dim: int = 1856
    shared_hidden_dim: int = 3712
    n_routed_experts: int = 128
    experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    tie_embeddings: bool = False
    state_dtype: Any = jnp.float32         # the SSM state as it is held
    dtype: Any = jnp.bfloat16              # activations
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.pattern) - set("ME*") or not self.pattern:
            raise ValueError(f"a pattern of M, E and * (got {self.pattern!r})")
        if self.mamba_heads % self.n_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("heads are shared by whole groups")
        if self.experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts a token than experts")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def d_inner(self) -> int:                # Mamba-2's x and z widths
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:               # x, B and C, convolved
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def kv_width(self) -> int:               # a token's row in the pool
        return self.n_kv_heads * self.head_dim

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)


CONFIGS = {
    # every kind of layer at toy widths, the published ratios kept: 8
    # Mamba-2 heads of 8 in 2 groups of state 16, 16 experts top-3 and a
    # shared one twice as wide, 4 query heads over 2 KV heads of 16;
    # chunks of 8, so a prompt crosses them
    "tiny": NemotronHConfig(
        vocab_size=256, dim=64, pattern="MEMEM*EME", n_heads=4,
        n_kv_heads=2, head_dim=16, mamba_heads=8, mamba_head_dim=8,
        n_groups=2, d_state=16, ssd_chunk=8, moe_hidden_dim=32,
        shared_hidden_dim=64, n_routed_experts=16, experts_per_tok=3,
        max_seq_len=256, dtype=jnp.float32, param_dtype=jnp.float32),
    "nemotron3_nano_30b_a3b": NemotronHConfig(),
}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _normal(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)


def _init_mamba(key, cfg: NemotronHConfig, n: int, fan_out: int):
    ks = jax.random.split(key, 5)
    d, D, H = cfg.param_dtype, cfg.dim, cfg.mamba_heads
    # Mamba-2's own initialisation: dt log-uniform in [time_step_min,
    # time_step_max] = [1e-3, 1e-1], floored at 1e-4, stored as the bias
    # softplus inverts to; A uniform in [1, 16]
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(ks[0], (n, H), _F32)
        * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)), 1e-4)
    return {
        "norm": jnp.ones((n, D), d),
        "in_proj": _normal(ks[1], (n, D, cfg.d_inner + cfg.conv_dim + H),
                           D, d),
        "conv_w": _normal(ks[2], (n, cfg.conv_dim, cfg.d_conv), cfg.d_conv,
                          d),
        "conv_b": jnp.zeros((n, cfg.conv_dim), d),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (n, H), _F32, 1.0, 16.0)),
        "D": jnp.ones((n, H), _F32),
        "gnorm": jnp.ones((n, cfg.d_inner), d),
        "out_proj": _normal(ks[4], (n, cfg.d_inner, D), cfg.d_inner * fan_out,
                            d)}


def _centred(a):
    """A down projection (.., f, dim) with every output's weights summing
    to zero over the expert's width. ``relu(.)^2`` is never negative
    (mean 0.5 a unit on a unit normal), so an uncentred random down
    projection adds one and the same vector to every token, layer after
    layer, until the routers see alike streams and pick alike experts
    (57 of 128 touched a decode step on the v5e, where 32 tokens over an
    even load touch 99); a trained model's load is even."""
    return a - jnp.mean(a, axis=-2, keepdims=True, dtype=_F32).astype(a.dtype)


def _init_moe(key, cfg: NemotronHConfig, n: int, fan_out: int):
    ks = jax.random.split(key, 6)
    d, D, E = cfg.param_dtype, cfg.dim, cfg.n_routed_experts
    F, Fs = cfg.moe_hidden_dim, cfg.shared_hidden_dim
    return {
        "norm": jnp.ones((n, D), d),
        "router": _normal(ks[0], (n, D, E), D, d),
        # the selection bias, float32, normal x 0.01 (latent_moe's reason)
        "router_bias": 0.01 * jax.random.normal(ks[1], (n, E), _F32),
        # the up projection stored transposed (``parallel.moe.relu2``)
        "w_up_t": _normal(ks[2], (n, E, F, D), D, d),
        "w_down": _centred(_normal(ks[3], (n, E, F, D), F * fan_out, d)),
        "ws_up": _normal(ks[4], (n, D, Fs), D, d),
        "ws_down": _centred(_normal(ks[5], (n, Fs, D), Fs * fan_out, d))}


def _init_attn(key, cfg: NemotronHConfig, n: int, fan_out: int):
    ks = jax.random.split(key, 4)
    d, D, hd = cfg.param_dtype, cfg.dim, cfg.head_dim
    return {
        "norm": jnp.ones((n, D), d),
        "wq": _normal(ks[0], (n, D, cfg.n_heads * hd), D, d),
        "wk": _normal(ks[1], (n, D, cfg.kv_width), D, d),
        "wv": _normal(ks[2], (n, D, cfg.kv_width), D, d),
        "wo": _normal(ks[3], (n, cfg.n_heads * hd, D),
                      cfg.n_heads * hd * fan_out, d)}


def init_params(cfg: NemotronHConfig, rng: Optional[jax.Array] = None):
    """Random weights, scaled by fan-in (out-projections by fan-in x 2 x
    layers; the experts' down projections centred, :func:`_centred`).
    Layers of one kind are stacked on a leading axis in the
    order the pattern meets them: ``mamba``, ``moe`` (the expert bank
    (layers, experts, ..), which the grouped product takes as it is
    stored) and ``attn``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 5)
    fan = 2 * cfg.n_layers
    params = {
        "tok_embed": _normal(ks[0], (cfg.vocab_size, cfg.dim), cfg.dim,
                             cfg.param_dtype),
        "mamba": _init_mamba(ks[1], cfg, cfg.count("M"), fan),
        "moe": _init_moe(ks[2], cfg, cfg.count("E"), fan),
        "attn": _init_attn(ks[3], cfg, cfg.count("*"), fan),
        "final_norm": jnp.ones((cfg.dim,), cfg.param_dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal(ks[4], (cfg.dim, cfg.vocab_size),
                                    cfg.dim, cfg.param_dtype)
    return params


def _layer(stack, i):
    """Layer ``i`` of a stack of one kind, bank and all: a static slice,
    which XLA reads inside the products that take it."""
    return {n: a[i] for n, a in stack.items()}


def _final(cfg, params, x):
    return llama._lm_head(cfg, params, rms_norm(x, params["final_norm"],
                                                cfg.norm_eps))


# ---------------------------------------------------------------------------
# the pieces of a layer
# ---------------------------------------------------------------------------
def _mamba_inputs(cfg: NemotronHConfig, lp, h, tail):
    """Everything of the Mamba-2 mixer in front of the recurrence, for h
    (b, s, dim) and the convolution's tail (b, d_conv-1, conv_dim): (x
    (b, s, H, p), dt float32 (b, s, H), B, C (b, s, G, n), z (b, s, H p),
    padded: the tail and the convolution's inputs end to end)."""
    b, s, _ = h.shape
    Di, G, N = cfg.d_inner, cfg.n_groups, cfg.d_state
    proj = h @ lp["in_proj"]
    z, xbc, dt = jnp.split(proj, [Di, Di + cfg.conv_dim], axis=-1)
    y, padded = causal_conv1d(xbc, lp["conv_w"], lp["conv_b"], tail)
    xbc = jax.nn.silu(y).astype(cfg.dtype)
    x, B, C = jnp.split(xbc, [Di, Di + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(_F32) + lp["dt_bias"].astype(_F32))
    return (x.reshape(b, s, cfg.mamba_heads, cfg.mamba_head_dim), dt,
            B.reshape(b, s, G, N), C.reshape(b, s, G, N), z, padded)


def _mamba_out(cfg: NemotronHConfig, lp, y, z):
    """The gate, the group RMSNorm after it, the out-projection: y
    (b, s, H p) float32 -> (b, s, dim)."""
    b, s, _ = y.shape
    g = (y * jax.nn.silu(z.astype(_F32))).reshape(b, s, cfg.n_groups, -1)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
    g = g.reshape(b, s, -1).astype(cfg.dtype) * lp["gnorm"].astype(cfg.dtype)
    return g @ lp["out_proj"]


def _neg_exp(a_log):
    return -jnp.exp(a_log.astype(_F32))


def _shared_expert(lp, h):
    """The shared expert, non-gated as the routed ones: h (T, dim)."""
    with jax.named_scope(moe.SHARED_SCOPE):
        return moe.relu2(h @ lp["ws_up"]) @ lp["ws_down"]


def _moe_ffn(cfg: NemotronHConfig, params, at, h, valid):
    """Expert layer ``at`` (of the ``moe`` stack) on h (b, s, dim): (delta
    (b, s, dim), counts (3,) int32 = assignments, experts touched, the
    busiest expert's load; the router's picks (b s, top_k)). The bank
    goes to the grouped product whole, with the layer's index: cut into
    a layer's slab it would be copied, 2.6 GB a layer at the published
    widths. ``valid`` (b, s) says which tokens are real."""
    b, s, D = h.shape
    lp = params["moe"]
    x = h.reshape(b * s, D)
    idx, w = moe.route_sigmoid(
        x, lp["router"][at], lp["router_bias"][at],
        top_k=cfg.experts_per_tok, renorm=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor)
    y, sizes = moe.moe_ffn_dropless(
        {n: lp[n] for n in ("w_up_t", "w_down")}, x, idx, w,
        layer=jnp.int32(at), valid=valid.reshape(b * s))
    y = y + _shared_expert({n: lp[n][at] for n in ("ws_up", "ws_down")}, x)
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()])
    return y.reshape(b, s, D), counts.astype(jnp.int32), idx


def _qkv(cfg: NemotronHConfig, lp, h, positions):
    """h (b, s, dim) at ``positions`` (b, s) -> q (b, n_heads, s, hd); k,
    v as the pool stores them, (b, s, n_kv_heads hd), a token's heads
    end to end. No position encoding: ``positions`` is for a check that
    puts another projection in this one's place."""
    del positions
    b, s, _ = h.shape
    with jax.named_scope("qkv_proj"):
        q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim) \
            .transpose(0, 2, 1, 3)
        return q, h @ lp["wk"], h @ lp["wv"]


@jax.named_scope("out_proj")
def _out_proj(cfg: NemotronHConfig, lp, o):
    """o (b, n_heads, s, hd) -> (b, s, dim)."""
    b, H, s, hd = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, H * hd).astype(cfg.dtype) \
        @ lp["wo"]


# ---------------------------------------------------------------------------
# whole sequences: forward and the prefills
# ---------------------------------------------------------------------------
def _kv_block(s: int) -> int:
    """Keys a step of the prefill attention's loop reads, for a run of s
    queries; the stage's capacity is a multiple of it."""
    return min(512, s)


def _fresh_stage(cfg: NemotronHConfig, b: int, capacity: int):
    """Empty carried state of b sequences: the attention layers' rows
    (A, b, capacity, kv_width), the Mamba layers' tails and states."""
    rows = (cfg.count("*"), b, capacity, cfg.kv_width)
    M = cfg.count("M")
    return {"k": jnp.zeros(rows, cfg.dtype), "v": jnp.zeros(rows, cfg.dtype),
            "conv": jnp.zeros((M, b, cfg.d_conv - 1, cfg.conv_dim),
                              cfg.dtype),
            "ssm": jnp.zeros((M, b, cfg.mamba_heads, cfg.mamba_head_dim,
                              cfg.d_state), cfg.state_dtype)}


def _mamba_seq(cfg: NemotronHConfig, lp, x, n_valid, tail0, state0):
    """A Mamba-2 layer's mixer over x (b, s, dim), s a multiple of
    ``ssd_chunk``, from a convolution tail and a state. Positions from
    ``n_valid`` on are padding: their step is zero, so the state handed
    on is the one after position ``n_valid - 1``. Returns (the mixer's
    output, tail, state, the recurrence's inputs)."""
    b, s, _ = x.shape
    h = rms_norm(x, lp["norm"], cfg.norm_eps)
    with jax.named_scope(SSM_SCOPE):
        xs, dt, B, C, z, padded = _mamba_inputs(cfg, lp, h, tail0)
        dt = jnp.where((jnp.arange(s) < n_valid)[None, :, None], dt, 0.0)
        with jax.named_scope(SSM_STATE_SCOPE):
            y, state = ssd_chunked(xs, dt, _neg_exp(lp["A_log"]), B, C,
                                   lp["D"], state0, chunk=cfg.ssd_chunk)
        # the d_conv-1 inputs before position n_valid
        tail = lax.dynamic_slice_in_dim(padded, n_valid, cfg.d_conv - 1,
                                        axis=1)
        out = _mamba_out(cfg, lp, y.reshape(b, s, -1), z)
    return out, tail, state, (xs, dt, B, C)


def _sequence(cfg: NemotronHConfig, params, tokens, start, n_valid, stage,
              seen=None):
    """Every layer over tokens (b, s) at positions ``start ..`` (a traced
    scalar), ``s`` a multiple of ``ssd_chunk``, from the carried
    ``stage``: each Mamba layer goes on from its tail and state (from
    nothing at ``start`` 0, whatever the stage holds), each attention
    layer writes the tokens' keys and values into the stage's rows at
    ``start`` and attends causally by absolute position over rows ``[0,
    start + s)``. Tokens from ``n_valid`` on are padding. Returns (x (b,
    s, dim), the new stage, counts (3,) over the expert layers).
    ``seen``: a dict that gets the routers' picks, the stream entering
    every layer and the first Mamba layer's recurrence inputs (what only
    a check reads)."""
    b, s = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    fresh = start == 0
    pos = jnp.broadcast_to(start + jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = jnp.broadcast_to(jnp.arange(s) < n_valid, (b, s))
    z = jnp.zeros((), jnp.int32)
    conv = jnp.where(fresh, 0, stage["conv"]).astype(cfg.dtype)
    ssm = jnp.where(fresh, 0, stage["ssm"])
    rows_k, rows_v = stage["k"], stage["v"]
    tails, states, picks, streams = [], [], [], []
    counts = jnp.zeros((3,), jnp.int32)
    x = llama._embed(cfg, params, tokens)
    at = {"M": 0, "E": 0, "*": 0}
    for kind in cfg.pattern:
        i = at[kind]
        at[kind] += 1
        streams.append(x)
        if kind == "M":
            out, tail, state, inputs = _mamba_seq(
                cfg, _layer(params["mamba"], i), x, n_valid, conv[i], ssm[i])
            if i == 0 and seen is not None:
                seen["ssm_inputs"] = inputs
            tails.append(tail)
            states.append(state.astype(stage["ssm"].dtype))
            x = x + out
        elif kind == "E":
            h = rms_norm(x, params["moe"]["norm"][i], cfg.norm_eps)
            delta, c, p = _moe_ffn(cfg, params, i, h, valid)
            picks.append(p)
            counts = counts + c
            x = x + delta
        else:
            lp = _layer(params["attn"], i)
            q, k, v = _qkv(cfg, lp, rms_norm(x, lp["norm"], cfg.norm_eps),
                           pos)
            with jax.named_scope(KV_WRITE_SCOPE):
                where = (jnp.int32(i), z, start, z)
                rows_k = lax.dynamic_update_slice(
                    rows_k, k[None].astype(rows_k.dtype), where)
                rows_v = lax.dynamic_update_slice(
                    rows_v, v[None].astype(rows_v.dtype), where)
            with jax.named_scope(ATTENTION_SCOPE):
                o = block_causal_rows_attention(
                    q, rows_k, rows_v, layer=i, q_offset=start, block=1,
                    scale=cfg.scale, kv_block=_kv_block(s))
            x = x + _out_proj(cfg, lp, o)
    if seen is not None:
        seen["picks"] = jnp.stack(picks) if picks else None
        seen["streams"] = jnp.stack(streams + [x])
    new = {"k": rows_k, "v": rows_v, "conv": jnp.stack(tails),
           "ssm": jnp.stack(states)}
    return x, new, counts


def _padded(cfg: NemotronHConfig, tokens):
    """tokens (b, s) end-padded to whole SSD chunks, and a stage of whole
    key blocks for them."""
    b, s = tokens.shape
    tokens = jnp.pad(tokens, ((0, 0), (0, -s % cfg.ssd_chunk)))
    blk = _kv_block(tokens.shape[1])
    return tokens, _fresh_stage(cfg, b, -(-tokens.shape[1] // blk) * blk)


def _seen(cfg: NemotronHConfig, params, tokens):
    """What a pass like :func:`forward`'s hands a check."""
    s = tokens.shape[1]
    padded, stage = _padded(cfg, tokens)
    seen = {}
    _sequence(cfg, params, padded, 0, s, stage, seen)
    return seen, s


def forward(cfg: NemotronHConfig, params, tokens):
    """tokens (b, s) -> logits (b, s, V) float32: every layer on every
    position, no cache."""
    s = tokens.shape[1]
    padded, stage = _padded(cfg, tokens)
    x, _, _ = _sequence(cfg, params, padded, 0, s, stage)
    return _final(cfg, params, x[:, :s])


def router_picks(cfg: NemotronHConfig, params, tokens):
    """The experts every expert layer's router chose for tokens (b, s) in
    a pass like :func:`forward`'s: (expert layers, b s, top_k)."""
    seen, s = _seen(cfg, params, tokens)
    b = tokens.shape[0]
    picks = seen["picks"]
    return picks.reshape(picks.shape[0], b, -1, picks.shape[-1])[:, :, :s] \
        .reshape(picks.shape[0], b * s, -1)


def layer_streams(cfg: NemotronHConfig, params, tokens):
    """The residual stream entering every layer, and leaving the last,
    in a pass like :func:`forward`'s: (L + 1, b, s, dim)."""
    seen, s = _seen(cfg, params, tokens)
    return seen["streams"][:, :, :s]


def ssm_inputs(cfg: NemotronHConfig, params, tokens, start, n_valid, stage):
    """What the FIRST Mamba layer's recurrence is handed for a prompt's
    chunk, tokens (1, chunk) at positions ``start ..``, ``n_valid`` of
    them real, from the stage as the chunks before left it:
    :func:`prefill_slot_paged_chunk`'s arithmetic at its shapes, in the
    program's own precision. Returns ((x (1, chunk, H, p), dt float32
    (1, chunk, H), B, C (1, chunk, G, n)), the stage it hands on). What a
    check holds the recurrence's state against a plain one with, on the
    same inputs."""
    seen = {}
    _, stage, _ = _sequence(cfg, params, tokens, start, n_valid, stage, seen)
    return seen["ssm_inputs"], stage


# ---------------------------------------------------------------------------
# serving state and programs
# ---------------------------------------------------------------------------
def decode_attention_path(cfg, kv, mesh=None, *, verify: bool = False) -> str:
    """Which attention :func:`decode_slots_paged` reads the pool through,
    over the state ``kv`` (arrays or shapes): ``ops.attention.
    rows_decode_path``'s answer (``"pages"``: the walk over live pages
    where they lie; ``"gathered"``: every slot's rows copied out)."""
    del verify
    return rows_decode_path(
        (kv["ssm"].shape[1], cfg.n_heads, 1, cfg.head_dim), kv["k"].shape,
        kv["k"].dtype, mesh=mesh)


def init_paged_cache(cfg: NemotronHConfig, max_slots: int, n_pages: int,
                     page_size: int, mesh=None, int8: bool = False):
    """Device state for the paged serving engine, two kinds side by side:
    ``k``/``v`` the attention layers' page pools, (A, n_pages,
    page_size, n_kv_heads hd), token-major (``llama.init_paged_cache``'s
    layout, so ``copy_page`` is ``llama``'s) with a token's heads end to
    end (2 heads of 128 laid out as (.., 2, 128) would be padded to
    (8, 128) tiles); ``conv`` (M, slots, d_conv-1, conv_dim) and ``ssm``
    (M, slots, H, p, n) in ``state_dtype``, the Mamba layers' state. Plus
    the per-slot ``lengths``/``tokens``/``rngs`` of every family. Page
    tables stay on the host."""
    if mesh is not None or int8:
        raise ValueError("nemotron_h: " + SERVE_UNSUPPORTED[
            "mesh" if mesh is not None else "int8_pages"])
    pool = (cfg.count("*"), n_pages, page_size, cfg.kv_width)
    M = cfg.count("M")
    return {
        "k": jnp.zeros(pool, cfg.dtype), "v": jnp.zeros(pool, cfg.dtype),
        "conv": jnp.zeros((M, max_slots, cfg.d_conv - 1, cfg.conv_dim),
                          cfg.dtype),
        "ssm": jnp.zeros((M, max_slots, cfg.mamba_heads, cfg.mamba_head_dim,
                          cfg.d_state), cfg.state_dtype),
        "lengths": jnp.zeros((max_slots,), jnp.int32),
        "tokens": jnp.zeros((max_slots,), jnp.int32),
        "rngs": jnp.zeros((max_slots, 2), jnp.uint32)}


copy_page = llama.copy_page       # pools only; the fixed state has no pages


def _mamba_step(cfg: NemotronHConfig, lp, x, conv, ssm, i, active):
    """Mamba layer ``i`` for one token of every slot. x: (S, 1, dim);
    conv, ssm: the whole state arrays, this layer's at ``i``, read and
    written in place. A slot that is not ``active`` keeps its tail and
    state as they are (its step is zero)."""
    h = rms_norm(x, lp["norm"], cfg.norm_eps)
    with jax.named_scope(SSM_SCOPE):
        tail = conv[i]
        xs, dt, B, C, z, padded = _mamba_inputs(cfg, lp, h, tail)
        dt = jnp.where(active[:, None], dt[:, 0], 0.0)
        with jax.named_scope(SSM_STATE_SCOPE):
            y, state = ssd_step(ssm[i], xs[:, 0], dt, _neg_exp(lp["A_log"]),
                                B[:, 0], C[:, 0], lp["D"])
            ssm = ssm.at[i].set(state.astype(ssm.dtype))
        conv = conv.at[i].set(jnp.where(active[:, None, None], padded[:, 1:],
                                        tail))
        out = _mamba_out(cfg, lp, y.reshape(y.shape[0], 1, -1), z)
    return x + out, conv, ssm


def _attend(cfg: NemotronHConfig, q, ck, cv, page_table, lengths, layer,
            path: str):
    """A decode step's attention of q (S, n_heads, 1, hd) over layer
    ``layer`` of the pools, keys ``[0, lengths)`` a slot, by ``path``
    (:func:`decode_attention_path`'s answer)."""
    if path == "pages":
        from ..ops.paged_attention import paged_attention_rows
        with jax.named_scope(ATTENTION_SCOPE):
            return paged_attention_rows(q, ck, cv, page_table, lengths,
                                        layer=layer, scale=cfg.scale)
    return gathered_rows_decode_attention(q, ck, cv, page_table, lengths,
                                          layer=layer, scale=cfg.scale)


def decode_logits(cfg: NemotronHConfig, params, kv, sv, active, page_table,
                  mesh=None):
    """The decode step up to its logits: (logits (S, V) float32, the new
    kv, counts (3,) int32 over the active slots' tokens)."""
    ck, cv, conv, ssm = kv["k"], kv["v"], kv["conv"], kv["ssm"]
    ps = ck.shape[2]
    cap = page_table.shape[1] * ps
    pos = jnp.minimum(sv["lengths"].astype(jnp.int32), cap - 1)
    nslots = page_table.shape[0]
    phys = page_table[jnp.arange(nslots), pos // ps]
    counts = jnp.zeros((3,), jnp.int32)
    path = decode_attention_path(cfg, kv, mesh)
    x = llama._embed(cfg, params, sv["tokens"][:, None])
    at = {"M": 0, "E": 0, "*": 0}
    for kind in cfg.pattern:
        i = at[kind]
        at[kind] += 1
        if kind == "M":
            x, conv, ssm = _mamba_step(cfg, _layer(params["mamba"], i), x,
                                       conv, ssm, i, active)
        elif kind == "E":
            h = rms_norm(x, params["moe"]["norm"][i], cfg.norm_eps)
            delta, c, _ = _moe_ffn(cfg, params, i, h, active[:, None])
            counts = counts + c
            x = x + delta
        else:
            lp = _layer(params["attn"], i)
            q, k, v = _qkv(cfg, lp, rms_norm(x, lp["norm"], cfg.norm_eps),
                           pos[:, None])
            with jax.named_scope(KV_WRITE_SCOPE):
                ck, cv = llama._write_pages(ck, cv, k[:, 0], v[:, 0], i,
                                            phys, pos % ps)
            o = _attend(cfg, q, ck, cv, page_table, pos + 1, i, path)
            x = x + _out_proj(cfg, lp, o)
    return _final(cfg, params, x)[:, 0], {"k": ck, "v": cv, "conv": conv,
                                          "ssm": ssm}, counts


def decode_slots_paged(cfg: NemotronHConfig, params, kv, sv, active,
                       page_table, temperature, top_k, top_p, mesh=None):
    """ONE decode step over the bank: ``llama.decode_slots_paged``'s
    contract (same arguments, same sampling and rng chains). A slot that
    is not ``active`` flows through (fixed shape), writes to scratch page
    0, keeps its Mamba state and is routed to no expert. Returns
    (sampled tokens (S,) with ``STEP_COUNTS``' values behind them, (S +
    3,) int32; new kv; new sv)."""
    logits, new_kv, counts = decode_logits(cfg, params, kv, sv, active,
                                           page_table, mesh)
    new_rngs, sampled = llama._sample_slots(
        sv["rngs"], logits, temperature, top_k, top_p)
    share = (counts[2].astype(_F32) * 1e6
             / jnp.maximum(counts[0], 1).astype(_F32)).astype(jnp.int32)
    out = jnp.concatenate([sampled, counts[:2], share[None]])
    return out, new_kv, {
        "lengths": sv["lengths"].astype(jnp.int32) + active.astype(jnp.int32),
        "tokens": sampled, "rngs": new_rngs}


@jax.named_scope(KV_WRITE_SCOPE)
def _seat_state(kv, stage, pages_row, slot):
    """A prefilled prompt's state into ``slot``: ALL of the slot's tails
    and states (whatever request held them before), and the stage's
    keys and values (A, 1, capacity, width) into the slot's pages;
    entries of ``pages_row`` past the granted ones name scratch page 0,
    which is never attended."""
    ps = kv["k"].shape[2]
    out = dict(kv)
    for name in ("k", "v"):
        rows = stage[name][:, 0]
        rows = jnp.pad(rows, ((0, 0), (0, -rows.shape[1] % ps), (0, 0)))
        pages = rows.reshape(rows.shape[0], rows.shape[1] // ps, ps,
                             rows.shape[-1])
        out[name] = kv[name].at[:, pages_row[:pages.shape[1]]].set(
            pages.astype(kv[name].dtype))
    for name in ("conv", "ssm"):
        out[name] = lax.dynamic_update_index_in_dim(
            kv[name], stage[name][:, 0].astype(kv[name].dtype), slot, 1)
    return out


def _seat_first(cfg, params, x, stage, n_valid, true_len, pages_row, slot,
                kv, sv, rng, temperature, top_k, top_p):
    """The end of an admission: the logits of position ``n_valid - 1`` of
    x (1, s, dim), the first token sampled from them, the prompt's state
    seated, the slot's length, token and rng chain set. Returns (first
    token (1,), new kv, new sv)."""
    last = lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = _final(cfg, params, last)[:, 0]
    rng, sub = jax.random.split(rng)
    tok = llama.sample_logits(sub, logits, temperature=temperature,
                              top_k=top_k, top_p=top_p)
    z = jnp.zeros((), jnp.int32)
    new_sv = {
        "lengths": lax.dynamic_update_slice(
            sv["lengths"].astype(jnp.int32), true_len[None], (slot,)),
        "tokens": lax.dynamic_update_slice(
            sv["tokens"], tok.astype(sv["tokens"].dtype), (slot,)),
        "rngs": lax.dynamic_update_slice(
            sv["rngs"], rng[None].astype(sv["rngs"].dtype), (slot, z))}
    return tok, _seat_state(kv, stage, pages_row, slot), new_sv


def prefill_slot_paged(cfg: NemotronHConfig, params, tokens, true_len,
                       prefix_len, pages_row, slot, kv, sv, rng,
                       temperature, top_k, top_p, mesh=None):
    """Admission: ``llama.prefill_slot_paged``'s contract, cold only
    (``prefix_len`` is 0: the engine refuses a prefix cache for this
    family). Every layer over the prompt (END-padded to its bucket; the
    padding moves no state and is routed nowhere), the head on the last
    position alone; the slot's state is overwritten whole. Returns
    (first token (1,), new kv, new sv)."""
    del prefix_len, mesh
    true_len = jnp.asarray(true_len, jnp.int32)
    padded, stage = _padded(cfg, tokens)
    x, stage, _ = _sequence(cfg, params, padded, 0, true_len, stage)
    return _seat_first(cfg, params, x, stage, true_len, true_len, pages_row,
                       jnp.asarray(slot, jnp.int32), kv, sv, rng,
                       temperature, top_k, top_p)


# -- a prompt in chunks: the stall a running request sees is one chunk's ----
def init_prefill_stage(cfg: NemotronHConfig, capacity: int, chunk: int):
    """Where a prompt that is prefilled ``chunk`` tokens at a time keeps
    what its chunks hand on, outside the slot bank (a decode step in
    between computes on every slot): the attention layers' keys and
    values of up to ``capacity`` positions, one slot's tails and
    states. ``prefill_slot_paged_last`` seats it."""
    if (capacity % chunk or chunk % _kv_block(chunk)
            or chunk % cfg.ssd_chunk):
        raise ValueError(
            f"a prefill chunk ({chunk}) divides the slot's capacity "
            f"({capacity}) and is a whole number of key blocks "
            f"({_kv_block(chunk)}) and of SSD chunks ({cfg.ssd_chunk})")
    return _fresh_stage(cfg, 1, capacity)


def prefill_slot_paged_chunk(cfg: NemotronHConfig, params, tokens, start,
                             stage, mesh=None):
    """One whole chunk of a prompt that is not its last: every layer
    over tokens (1, chunk) at positions ``start ..``, from the stage as
    the chunks before left it (``start`` 0: from nothing) to the stage
    the next chunk goes on from. The slot bank is not touched."""
    del mesh
    _, stage, _ = _sequence(cfg, params, tokens, start, tokens.shape[1],
                            stage)
    return stage


def prefill_slot_paged_last(cfg: NemotronHConfig, params, tokens, start,
                            n_valid, stage, pages_row, slot, kv, sv, rng,
                            temperature, top_k, top_p, mesh=None):
    """A prompt's last chunk, ``n_valid`` tokens END-padded to tokens
    (1, chunk), at positions ``start ..``; then the admission's end as
    ``prefill_slot_paged``'s: the whole prompt's state seated into the
    slot, the first token sampled. Returns (first token (1,), new kv,
    new sv)."""
    del mesh
    start = jnp.asarray(start, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    x, stage, _ = _sequence(cfg, params, tokens, start, n_valid, stage)
    return _seat_first(cfg, params, x, stage, n_valid, start + n_valid,
                       pages_row, jnp.asarray(slot, jnp.int32), kv, sv, rng,
                       temperature, top_k, top_p)
