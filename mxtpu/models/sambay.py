"""SambaY: a decoder-hybrid-decoder with differential attention
(Ren et al., "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation", arXiv 2507.06607; the published
checkpoint is Phi-4-mini-flash-reasoning, ``model_type: phi4flash``).

``n_layers`` = 4p layers, every one ``x += mixer(LN(x)); x +=
MLP(LN(x))`` with LayerNorm (weight and bias), no positional encoding:

- the *self-decoder*, layers ``0 .. 2p+1``: ``p`` pairs of a Mamba (S6)
  layer and a sliding-window attention layer, then one more Mamba layer
  ("16" at the published depth) and the one full-attention layer ("17");
- the *cross-decoder*, layers ``2p+2 .. 4p-1``: ``p - 1`` pairs of a
  gated memory unit, which gates the last Mamba layer's scan output
  (before its own output gate), and a cross-attention layer, which has
  queries of its own and reads the full-attention layer's keys and
  values.

Every attention mixer is differential (Ye et al., arXiv 2410.05258):
query heads pair up (2i, 2i+1), KV heads (2j, 2j+1), and head i is
``softmax(q1 k1') v - lam * softmax(q2 k2') v`` over ``v = [v_2j;
v_2j+1]``. Here the pair is computed as ONE grouped-query attention
with heads of twice the size: ``k' = [k_2j; k_2j+1]`` is the cache as
it lies in memory, and ``q1' = [q1; 0]``, ``q2' = [0; q2]``, so
``q1'.k' = q1.k1`` and ``q2'.k' = q2.k2`` — two softmaxes over one V
out of the repo's ordinary attention kernels (``ops/attention.py``),
with keys and values read once.

Serving keeps three kinds of state (``init_paged_cache``): the full
layer's keys and values in a one-layer page pool (the only state that
grows with the sequence; the cross layers read it too: on a TPU every
read walks the slot's live pages where they lie, ``ops.paged_attention.
paged_attention_rows``; elsewhere the rows are gathered once a step), a
ring of the
last ``sliding_window`` keys and values per window layer and slot, and
the convolution tail and float32 scan state per Mamba layer and slot.
A prompt needs the self-decoder only, so the prefill program runs the
cross-decoder on the last position alone. The serving surface is
``llama.py``'s: ``init_params``, ``forward``, ``init_paged_cache``,
``prefill_slot_paged``, ``decode_slots_paged``, ``copy_page``. Because
every kind of state here is carried — the scan's, the rings', the
pool's — a prompt can also be prefilled a chunk at a time with decode
steps in between (the engine's ``prefill_chunk``):
``init_prefill_stage``, ``prefill_slot_paged_chunk`` for every chunk
but the last, ``prefill_slot_paged_last`` for the last.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (ATTENTION_SCOPE, KV_GATHER_SCOPE,
                             blockwise_attention, flash_attention,
                             rows_decode_path, slot_decode_attention,
                             window_attention)
from ..ops.ssm import causal_conv1d, selective_scan, selective_scan_step
from . import llama
from .llama import KV_WRITE_SCOPE

__all__ = ["SambaYConfig", "CONFIGS", "init_params", "forward",
           "init_paged_cache", "prefill_slot_paged",
           "init_prefill_stage", "prefill_slot_paged_chunk",
           "prefill_slot_paged_last", "decode_slots_paged", "copy_page",
           "decode_attention_path"]

# the named scopes of this family's programs, besides the ones it
# shares with llama.py (embed and lm_head are llama.py's own functions;
# norm, mlp, kv_write, kv_gather, attention — here the full layer's —,
# sampler). Each is opened at the top level of its layer, so a trace
# reader that takes the first name of an operation's scope path finds
# it.
SSM_SCOPE = "ssm"
WINDOW_SCOPE = "window_attention"
CROSS_SCOPE = "cross_attention"
GMU_SCOPE = "gmu"

# what ``ServeEngine`` cannot do for this family yet, by option, with
# the mechanism in the way (the engine raises with these words)
SERVE_UNSUPPORTED = {
    "prefix_cache": "a prefix hit would need a snapshot of the recurrent "
                    "(SSM and convolution) state and of the window rings "
                    "at the shared boundary, and pages hold keys and "
                    "values only",
    "speculate_k": "a rejected draft would need the recurrent state and "
                   "the window rings rolled back to the last accepted "
                   "token",
    "int8_pages": "the page pool is shared by eight attention reads and "
                  "has no quantised form yet",
    "submit_prefilled": "a disaggregated hand-off carries keys and values"
                        " only, not the recurrent state or the rings",
    "mesh": "no sharding rules for the recurrent state yet",
}
# which kind of state each donated array is (the engine's byte gauges)
STATE_KINDS = {"k": "kv_pages", "v": "kv_pages",
               "wk": "window_ring", "wv": "window_ring",
               "conv": "ssm", "ssm": "ssm"}

_F32 = jnp.float32


@dataclass(frozen=True)
class SambaYConfig:
    family: ClassVar[str] = "sambay"
    vocab_size: int = 200064
    dim: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    hidden_dim: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2            # a Mamba layer every second layer
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    # the published config.json has no key for these: Mamba's defaults
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None    # None: ceil(dim / 16)
    dtype: Any = jnp.bfloat16        # activations
    param_dtype: Any = jnp.bfloat16
    scan_chunk: int = 8              # steps per chunk of the prefill scan

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("n_layers must be a multiple of 4, at least "
                             f"8 (got {self.n_layers})")
        if self.mb_per_layer != 2:
            raise ValueError("only mb_per_layer = 2 is written down")
        if self.n_heads % 4 or self.n_kv_heads % 2 \
                or (self.n_heads // 2) % (self.n_kv_heads // 2):
            raise ValueError("differential attention pairs up heads: "
                             f"{self.n_heads} / {self.n_kv_heads}")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.dim // 16)

    @property
    def n_pairs(self) -> int:        # Mamba + window pairs
        return self.n_layers // 4

    @property
    def n_cross(self) -> int:        # GMU + cross-attention pairs
        return self.n_layers // 4 - 1

    @property
    def kv_pairs(self) -> int:       # differential KV heads
        return self.n_kv_heads // 2


CONFIGS = {
    # every kind of layer at toy widths: 3 Mamba+window pairs, the
    # Mamba and full layers "6/7", 2 GMU+cross pairs
    "tiny": SambaYConfig(vocab_size=256, dim=64, n_layers=12, n_heads=8,
                         n_kv_heads=4, hidden_dim=128, sliding_window=8,
                         max_seq_len=256, dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_chunk=8),
    "phi4_mini_flash": SambaYConfig(),
}


def lambda_init(layer):
    """Differential attention's ``lam_init`` at 0-based depth
    ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _normal(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)


def _init_block(key, cfg: SambaYConfig, n: int):
    """What every layer has: two LayerNorms and the MLP (gate and up
    fused)."""
    k1, k2 = jax.random.split(key)
    d, D, H = cfg.param_dtype, cfg.dim, cfg.hidden_dim
    return {"norm1_w": jnp.ones((n, D), d), "norm1_b": jnp.zeros((n, D), d),
            "norm2_w": jnp.ones((n, D), d), "norm2_b": jnp.zeros((n, D), d),
            "w_gate_up": _normal(k1, (n, D, 2 * H), D, d),
            "w_down": _normal(k2, (n, H, D), H * 2 * cfg.n_layers, d)}


def _init_mamba(key, cfg: SambaYConfig, n: int):
    ks = jax.random.split(key, 7)
    d, D, Di = cfg.param_dtype, cfg.dim, cfg.d_inner
    N, R, K = cfg.d_state, cfg.rank, cfg.d_conv
    # Mamba's own initialisation of the step size: dt log-uniform in
    # [1e-3, 1e-1], stored as the bias softplus inverts to; A = -(1..N)
    step = jnp.exp(jax.random.uniform(ks[5], (n, Di), _F32)
                   * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    out = _init_block(ks[6], cfg, n)
    out.update({
        "in_proj": _normal(ks[0], (n, D, 2 * Di), D, d),
        "conv_w": _normal(ks[1], (n, Di, K), K, d),
        "conv_b": jnp.zeros((n, Di), d),
        "x_proj": _normal(ks[2], (n, Di, R + 2 * N), Di, d),
        "dt_proj": _normal(ks[3], (n, R, Di), R, d),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(_F32),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=_F32)), (n, Di, N)),
        "D": jnp.ones((n, Di), _F32),
        "out_proj": _normal(ks[4], (n, Di, D), Di * 2 * cfg.n_layers, d)})
    return out


def _init_attn(key, cfg: SambaYConfig, n: int, cross: bool = False):
    ks = jax.random.split(key, 9)
    d, D, hd = cfg.param_dtype, cfg.dim, cfg.head_dim
    out = _init_block(ks[8], cfg, n)
    out.update({
        "wq": _normal(ks[0], (n, D, cfg.n_heads * hd), D, d),
        "wo": _normal(ks[3], (n, cfg.n_heads * hd, D),
                      cfg.n_heads * hd * 2 * cfg.n_layers, d),
        "subln_w": jnp.ones((n, 2 * hd), d)})
    for i, name in enumerate(("lam_q1", "lam_k1", "lam_q2", "lam_k2")):
        out[name] = 0.1 * jax.random.normal(ks[4 + i], (n, hd), _F32)
    if not cross:
        out["wk"] = _normal(ks[1], (n, D, cfg.n_kv_heads * hd), D, d)
        out["wv"] = _normal(ks[2], (n, D, cfg.n_kv_heads * hd), D, d)
    return out


def _init_gmu(key, cfg: SambaYConfig, n: int):
    k1, k2, k3 = jax.random.split(key, 3)
    d, D, Di = cfg.param_dtype, cfg.dim, cfg.d_inner
    out = _init_block(k3, cfg, n)
    out.update({"in_proj": _normal(k1, (n, D, Di), D, d),
                "out_proj": _normal(k2, (n, Di, D),
                                    Di * 2 * cfg.n_layers, d)})
    return out


def init_params(cfg: SambaYConfig, rng: Optional[jax.Array] = None):
    """Random weights, scaled by fan-in. Layers of one kind are stacked
    on a leading axis: ``pairs`` (Mamba + window, ``n_pairs`` of them),
    ``mid`` (the last Mamba layer and the full-attention layer, one
    each) and ``cross`` (GMU + cross attention, ``n_cross``)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 8)
    P, C = cfg.n_pairs, cfg.n_cross
    one = partial(jax.tree_util.tree_map, lambda a: a[0])
    params = {
        "tok_embed": _normal(ks[0], (cfg.vocab_size, cfg.dim), cfg.dim,
                             cfg.param_dtype),
        "pairs": {"mamba": _init_mamba(ks[1], cfg, P),
                  "attn": _init_attn(ks[2], cfg, P)},
        "mid": {"mamba": one(_init_mamba(ks[3], cfg, 1)),
                "attn": one(_init_attn(ks[4], cfg, 1))},
        "cross": {"gmu": _init_gmu(ks[5], cfg, C),
                  "attn": _init_attn(ks[6], cfg, C, cross=True)},
        "final_norm_w": jnp.ones((cfg.dim,), cfg.param_dtype),
        "final_norm_b": jnp.zeros((cfg.dim,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal(ks[7], (cfg.dim, cfg.vocab_size),
                                    cfg.dim, cfg.param_dtype)
    return params


def _lam_inits(cfg: SambaYConfig):
    """``lam_init`` of the window layers (1, 3, ..), of the full layer
    and of the cross layers, by their depth in the whole stack."""
    P, C = cfg.n_pairs, cfg.n_cross
    return (jnp.asarray([lambda_init(2 * i + 1) for i in range(P)], _F32),
            lambda_init(2 * P + 1),
            jnp.asarray([lambda_init(2 * P + 3 + 2 * j)
                         for j in range(C)], _F32))


# ---------------------------------------------------------------------------
# the pieces of a layer
# ---------------------------------------------------------------------------
@jax.named_scope("norm")
def layer_norm(x, w, b, eps):
    x32 = x.astype(_F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    xc = x32 - mu
    inv = lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps)
    return (xc * inv * w.astype(_F32) + b.astype(_F32)).astype(x.dtype)


@jax.named_scope("mlp")
def _mlp(lp, h):
    gate, up = jnp.split(h @ lp["w_gate_up"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ lp["w_down"]


def _norm1(cfg, lp, x):
    """The LayerNorm in front of a layer's mixer."""
    return layer_norm(x, lp["norm1_w"], lp["norm1_b"], cfg.norm_eps)


def _residual_mlp(cfg, lp, x):
    return x + _mlp(lp, layer_norm(x, lp["norm2_w"], lp["norm2_b"],
                                   cfg.norm_eps))


def _final(cfg, params, x):
    """The last LayerNorm and llama.py's head (tied here): x (b, s, dim)
    -> logits (b, s, V) float32."""
    return llama._lm_head(cfg, params, layer_norm(
        x, params["final_norm_w"], params["final_norm_b"], cfg.norm_eps))


def _mamba_inputs(cfg: SambaYConfig, lp, h, tail):
    """Everything of the Mamba mixer in front of the scan, for h
    (b, s, dim) and the convolution's tail (b, d_conv-1, d_inner):
    (u, z, dt, B, C, padded) with u the scan's input, z the gate's,
    dt float32 (b, s, d_inner), and ``padded`` the tail and the
    convolution's inputs end to end."""
    N, R = cfg.d_state, cfg.rank
    u, z = jnp.split(h @ lp["in_proj"], 2, axis=-1)
    y, padded = causal_conv1d(u, lp["conv_w"], lp["conv_b"], tail)
    u = jax.nn.silu(y).astype(cfg.dtype)
    r, B, C = jnp.split(u @ lp["x_proj"], [R, R + N], axis=-1)
    dt = jax.nn.softplus((r @ lp["dt_proj"]).astype(_F32)
                         + lp["dt_bias"].astype(_F32))
    return u, z, dt, B, C, padded


def _mamba_out(cfg, lp, y, z):
    """The output gate and projection; y is the scan's output."""
    return (y * jax.nn.silu(z.astype(_F32))).astype(cfg.dtype) \
        @ lp["out_proj"]


def _neg_exp(a_log):
    return -jnp.exp(a_log.astype(_F32))


@jax.named_scope(GMU_SCOPE)
def _gmu(cfg, lp, h, memory):
    """Gated memory unit: the last Mamba layer's scan output, gated by
    this layer's own projection of its input."""
    return (jax.nn.silu(h @ lp["in_proj"]) * memory) @ lp["out_proj"]


def _diff_q(cfg: SambaYConfig, lp, h):
    """Queries of h (b, s, dim), each placed in its own half of a
    double-width head: (b, n_heads, s, 2 hd), head 2i = [q_2i; 0], head
    2i+1 = [0; q_2i+1] (see the module's docstring)."""
    b, s, _ = h.shape
    hd = cfg.head_dim
    q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads // 2, 2, 1, hd)
    q = q * jnp.eye(2, dtype=q.dtype)[:, :, None]   # (.., 2, 2, hd)
    return q.reshape(b, s, cfg.n_heads, 2 * hd).transpose(0, 2, 1, 3)


def _diff_kv(cfg: SambaYConfig, lp, h):
    """Keys and values of h (b, s, dim) as the caches store them:
    token-major, a token's heads end to end, (b, s, n_kv_heads hd)."""
    return h @ lp["wk"], h @ lp["wv"]


def _kv_heads(cfg: SambaYConfig, a):
    """Cached keys or values (b, s, n_kv_heads hd) as the attention
    kernels take them, KV heads paired: (b, kv_pairs, s, 2 hd)."""
    b, s, _ = a.shape
    return a.reshape(b, s, cfg.kv_pairs, 2 * cfg.head_dim) \
        .transpose(0, 2, 1, 3)


def _diff_out(cfg: SambaYConfig, lp, o, lam_init):
    """The two softmaxes' outputs o (b, n_heads, s, 2 hd) -> the
    mixer's output (b, s, dim): subtract within each pair, RMSNorm
    over the double head, scale, project."""
    b, _, s, hd2 = o.shape
    lam = (jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"]))
           - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + lam_init)
    o = o.astype(_F32).reshape(b, cfg.n_heads // 2, 2, s, hd2)
    a = o[:, :, 0] - lam * o[:, :, 1]
    a = a * lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + cfg.norm_eps)
    a = a * lp["subln_w"].astype(_F32) * (1.0 - lam_init)
    a = a.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads // 2 * hd2)
    return a.astype(cfg.dtype) @ lp["wo"]


def _scale(cfg):
    return 1.0 / math.sqrt(cfg.head_dim)


# ---------------------------------------------------------------------------
# whole sequences: forward and the prefill's self-decoder
# ---------------------------------------------------------------------------
def _mamba_seq(cfg: SambaYConfig, lp, x, true_len, tail0=None,
               state0=None):
    """A Mamba layer's mixer over x (b, s, dim), from an empty state
    or, for a later chunk of a prompt, from the convolution tail and
    scan state the chunk before it handed on. Positions from
    ``true_len`` on are padding: their step is zero, so the state
    handed on is the one after position ``true_len - 1``. Returns
    (mixer output, scan output before the gate, tail, state)."""
    b, s, _ = x.shape
    h = _norm1(cfg, lp, x)
    with jax.named_scope(SSM_SCOPE):
        if tail0 is None:
            tail0 = jnp.zeros((b, cfg.d_conv - 1, cfg.d_inner), cfg.dtype)
            state0 = jnp.zeros((b, cfg.d_inner, cfg.d_state), _F32)
        u, z, dt, B, C, padded = _mamba_inputs(cfg, lp, h, tail0)
        dt = jnp.where((jnp.arange(s) < true_len)[None, :, None], dt, 0.0)
        y, state = selective_scan(
            u, dt, _neg_exp(lp["A_log"]), B, C, lp["D"], state0,
            chunk=cfg.scan_chunk)
        # the d_conv-1 inputs before position true_len
        tail = lax.dynamic_slice_in_dim(padded, true_len,
                                        cfg.d_conv - 1, axis=1)
        out = _mamba_out(cfg, lp, y, z)
    return out, y.astype(cfg.dtype), tail, state


def _ring_of(k, true_len, window):
    """The ring a decode step expects after a prompt: position p at
    ring index ``p % window``, for the last ``window`` positions below
    ``true_len``. k: (b, s, d) -> (b, window, d); entries no position
    reaches yet hold position 0's and are never counted."""
    r = jnp.arange(window)
    p = r + window * ((true_len - 1 - r) // window)
    return k[:, jnp.clip(p, 0, k.shape[1] - 1)]


def _self_decoder(cfg: SambaYConfig, params, x, true_len, carry=None):
    """Layers 0 .. 2p+1 over x (b, s, dim). Returns (x, the memory the
    GMUs read (b, s, d_inner), the full layer's keys and values
    (b, s, n_kv_heads hd), the serving state after position
    ``true_len - 1``: conv (p+1, b, ..), ssm, and the window rings wk,
    wv (p, b, window, n_kv_heads hd)).

    ``carry`` is None for a sequence from its start. For a later chunk
    of a prompt (b = 1) it is the prefill stage (``init_prefill_stage``)
    as the chunks before left it, plus ``start``, the chunk's first
    position, a multiple of the window: every Mamba layer goes on from
    its ``conv`` and ``ssm``; a window layer reads its ring (positions
    ``start - window .. start - 1`` in order) in front of the chunk's
    own keys; the full layer writes the chunk's keys and values into
    the stage's ``k``/``v`` at ``start`` and attends over all of it,
    causally by absolute position, and the whole stage comes back as
    its keys and values. At ``start`` 0 nothing before the chunk is
    seen, whatever the stage holds."""
    lam_win, lam_full, _ = _lam_inits(cfg)
    W, P = cfg.sliding_window, cfg.n_pairs
    if carry is None:
        behind = None
        mid_state = (None, None)
    else:
        start = carry["start"]
        fresh = start == 0
        conv0 = jnp.where(fresh, 0, carry["conv"]).astype(cfg.dtype)
        ssm0 = jnp.where(fresh, 0.0, carry["ssm"])
        behind = (conv0[:P], ssm0[:P], carry["wk"], carry["wv"])
        mid_state = (conv0[P], ssm0[P])

    def pair(x, xs):
        lp, lam0, old = xs
        mp, ap = lp["mamba"], lp["attn"]
        out, _, tail, state = _mamba_seq(
            cfg, mp, x, true_len, *(old[:2] if old else ()))
        x = _residual_mlp(cfg, mp, x + out)
        h = _norm1(cfg, ap, x)
        with jax.named_scope(WINDOW_SCOPE):
            k, v = _diff_kv(cfg, ap, h)
            q = _diff_q(cfg, ap, h)
            seen, k_start = true_len, 0
            if old:
                # the ring in front: W more keys, W empty queries
                k = jnp.concatenate([old[2].astype(k.dtype), k], axis=1)
                v = jnp.concatenate([old[3].astype(v.dtype), v], axis=1)
                q = jnp.pad(q, ((0, 0), (0, 0), (W, 0), (0, 0)))
                seen, k_start = W + true_len, jnp.where(fresh, W, 0)
            o = window_attention(
                q, _kv_heads(cfg, k), _kv_heads(cfg, v), window=W,
                scale=_scale(cfg), block=min(512, W), k_start=k_start)
            out = _diff_out(cfg, ap, o[:, :, -x.shape[1]:], lam0)
            rings = (_ring_of(k, seen, W), _ring_of(v, seen, W))
        x = _residual_mlp(cfg, ap, x + out)
        return x, (tail, state) + rings

    x, (conv, ssm, wk, wv) = lax.scan(
        pair, x, (params["pairs"], lam_win, behind))
    mp, ap = params["mid"]["mamba"], params["mid"]["attn"]
    out, memory, tail, state = _mamba_seq(cfg, mp, x, true_len, *mid_state)
    x = _residual_mlp(cfg, mp, x + out)
    h = _norm1(cfg, ap, x)
    with jax.named_scope(ATTENTION_SCOPE):
        k, v = _diff_kv(cfg, ap, h)
        q = _diff_q(cfg, ap, h)
        if carry is None:
            o = flash_attention(q, _kv_heads(cfg, k), _kv_heads(cfg, v),
                                causal=True, scale=_scale(cfg))
        else:
            z = jnp.zeros((), jnp.int32)
            k = lax.dynamic_update_slice(
                carry["k"], k.astype(carry["k"].dtype), (z, start, z))
            v = lax.dynamic_update_slice(
                carry["v"], v.astype(carry["v"].dtype), (z, start, z))
            o = blockwise_attention(
                q, _kv_heads(cfg, k), _kv_heads(cfg, v), causal=True,
                scale=_scale(cfg), q_offset=start)
        out = _diff_out(cfg, ap, o, lam_full)
    x = _residual_mlp(cfg, ap, x + out)
    state = {"conv": jnp.concatenate([conv, tail[None]]),
             "ssm": jnp.concatenate([ssm, state[None]]),
             "wk": wk, "wv": wv}
    return x, memory, k, v, state


def _cross_decoder(cfg: SambaYConfig, params, x, memory, attend):
    """Layers 2p+2 .. 4p-1 over x (b, s, dim), with the memory at the
    same positions and ``attend(q) -> o``, the attention of queries
    (b, n_heads, s, 2 hd) over the full layer's keys and values."""
    def pair(x, xs):
        lp, lam0 = xs
        gp, ap = lp["gmu"], lp["attn"]
        h = _norm1(cfg, gp, x)
        x = _residual_mlp(cfg, gp, x + _gmu(cfg, gp, h, memory))
        h = _norm1(cfg, ap, x)
        with jax.named_scope(CROSS_SCOPE):
            out = _diff_out(cfg, ap, attend(_diff_q(cfg, ap, h)), lam0)
        return _residual_mlp(cfg, ap, x + out), None

    x, _ = lax.scan(pair, x, (params["cross"], _lam_inits(cfg)[2]))
    return x


def forward(cfg: SambaYConfig, params, tokens):
    """tokens (b, s) -> logits (b, s, V) float32: every layer on every
    position, no cache."""
    s = tokens.shape[1]
    x, memory, k, v, _ = _self_decoder(
        cfg, params, llama._embed(cfg, params, tokens), s)
    x = _cross_decoder(cfg, params, x, memory, lambda q: flash_attention(
        q, _kv_heads(cfg, k), _kv_heads(cfg, v), causal=True,
        scale=_scale(cfg)))
    return _final(cfg, params, x)


def prefill_logits(cfg: SambaYConfig, params, tokens, true_len,
                   carry=None):
    """What the prefill program computes for a prompt END-padded to
    tokens (1, bucket): the self-decoder over the prompt, the
    cross-decoder on position ``true_len - 1`` alone. Returns (logits
    (1, V) float32 of that position, the full layer's keys and values
    (1, bucket, n_kv_heads hd), the serving state). With ``carry``
    (``_self_decoder``'s) the tokens are a prompt's last chunk, the
    keys and values the stage's, the whole prompt's."""
    true_len = jnp.asarray(true_len, jnp.int32)
    x, memory, k, v, state = _self_decoder(
        cfg, params, llama._embed(cfg, params, tokens), true_len, carry)
    last = partial(lax.dynamic_slice_in_dim, start_index=true_len - 1,
                   slice_size=1, axis=1)
    kh, vh = _kv_heads(cfg, k), _kv_heads(cfg, v)
    seen = true_len if carry is None else carry["start"] + true_len
    x = _cross_decoder(
        cfg, params, last(x), last(memory),
        lambda q: slot_decode_attention(q, kh, vh, seen[None],
                                        scale=_scale(cfg)))
    return _final(cfg, params, x)[:, 0], k, v, state


# ---------------------------------------------------------------------------
# serving state and programs
# ---------------------------------------------------------------------------
def decode_attention_path(cfg, kv, mesh=None, *, verify: bool = False) -> str:
    """Which attention :func:`decode_slots_paged` builds its eight reads
    of the one shared pool on, over the state ``kv`` (arrays or shapes):
    ``ops.attention.rows_decode_path``'s answer for what the program
    hands it. ``"pages"``: the full layer and each cross layer walk the
    live pages where they lie (``ops.paged_attention.
    paged_attention_rows``), and nothing is gathered; ``"gathered"``:
    the pool's rows are copied out once a step (:func:`_gather_rows`)
    and read eight times. Static per compiled program; the engine
    exports it (``serve_decode_steps_total{attention}``,
    ``kv_cache_stats()``)."""
    del verify
    return rows_decode_path(
        (kv["wk"].shape[1], cfg.n_heads, 1, 2 * cfg.head_dim),
        kv["k"].shape, kv["k"].dtype, mesh=mesh)


def init_paged_cache(cfg: SambaYConfig, max_slots: int, n_pages: int,
                     page_size: int, mesh=None, int8: bool = False):
    """Device state for the paged serving engine, three kinds side by
    side: ``k``/``v`` the full-attention layer's page pool, (1, n_pages,
    page_size, n_kv_heads hd), token-major with a layer axis of one
    (``llama.init_paged_cache``'s layout, so ``copy_page`` is
    ``llama``'s) and a token's heads end to end (20 heads of 64 laid
    out as (.., 10, 128) or (.., 20, 64) would be padded by the chip's
    (8, 128) tiles, or relaid out around every write); ``wk``/``wv``
    the window layers' rings, (n_pairs, slots, sliding_window,
    n_kv_heads hd), position p at index ``p % sliding_window``;
    ``conv`` (n_pairs+1, slots, d_conv-1, d_inner) and ``ssm``
    (n_pairs+1, slots, d_inner, d_state) float32, the Mamba layers'
    state. Plus the per-slot ``lengths``/``tokens``/
    ``rngs`` of every family. Page tables stay on the host."""
    if mesh is not None or int8:
        raise ValueError("sambay: " + SERVE_UNSUPPORTED[
            "mesh" if mesh is not None else "int8_pages"])
    P, width = cfg.n_pairs, cfg.n_kv_heads * cfg.head_dim
    ring = (P, max_slots, cfg.sliding_window, width)
    pool = (1, n_pages, page_size, width)
    return {
        "k": jnp.zeros(pool, cfg.dtype), "v": jnp.zeros(pool, cfg.dtype),
        "wk": jnp.zeros(ring, cfg.dtype), "wv": jnp.zeros(ring, cfg.dtype),
        "conv": jnp.zeros((P + 1, max_slots, cfg.d_conv - 1, cfg.d_inner),
                          cfg.dtype),
        "ssm": jnp.zeros((P + 1, max_slots, cfg.d_inner, cfg.d_state),
                         _F32),
        "lengths": jnp.zeros((max_slots,), jnp.int32),
        "tokens": jnp.zeros((max_slots,), jnp.int32),
        "rngs": jnp.zeros((max_slots, 2), jnp.uint32)}


copy_page = llama.copy_page       # pools only; the fixed state has no pages


def _mamba_step(cfg: SambaYConfig, lp, x, conv, ssm, layer):
    """One Mamba layer for one token of every slot. x: (S, 1, dim);
    conv, ssm: the whole state arrays, this layer's at ``layer``."""
    h = _norm1(cfg, lp, x)
    with jax.named_scope(SSM_SCOPE):
        tail = lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
        state = lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
        u, z, dt, B, C, padded = _mamba_inputs(cfg, lp, h, tail)
        y, state = selective_scan_step(
            state, u[:, 0], dt[:, 0], _neg_exp(lp["A_log"]), B[:, 0],
            C[:, 0], lp["D"])
        conv = lax.dynamic_update_index_in_dim(conv, padded[:, 1:],
                                               layer, 0)
        ssm = lax.dynamic_update_index_in_dim(ssm, state, layer, 0)
        y = y[:, None]
        out = _mamba_out(cfg, lp, y, z)
    return _residual_mlp(cfg, lp, x + out), y.astype(cfg.dtype), conv, ssm


@jax.named_scope(KV_GATHER_SCOPE)
def _gather_rows(cfg, pool, page_table):
    """Every slot's pages of the one-layer pool -> (S, kv_pairs, cap,
    2 hd) rows: the gathered path's copy of capacity (a CPU, float32
    pools), made once a step and read by the full layer and every cross
    layer. The pages path never traces it."""
    g = pool.at[0, page_table].get(mode="promise_in_bounds")
    return _kv_heads(cfg, g.reshape(g.shape[0], -1, g.shape[-1]))


def decode_slots_paged(cfg: SambaYConfig, params, kv, sv, active,
                       page_table, temperature, top_k, top_p, mesh=None):
    """ONE decode step over the bank: ``llama.decode_slots_paged``'s
    contract (same arguments, same sampling and rng chains), with the
    three kinds of state in ``kv``. The layer loops are two scans (the
    Mamba+window pairs, the GMU+cross pairs) around layers "16/17", so
    the program does not grow with depth. Inactive slots compute on
    whatever their state holds and write to scratch page 0; a prefill
    overwrites all of a slot's state."""
    ps = kv["k"].shape[2]
    W = cfg.sliding_window
    cap = page_table.shape[1] * ps
    lengths = sv["lengths"].astype(jnp.int32)
    pos = jnp.minimum(lengths, cap - 1)       # the new token's position
    nslots = page_table.shape[0]
    at = jnp.arange(nslots)
    lam_win, lam_full, _ = _lam_inits(cfg)
    x = llama._embed(cfg, params, sv["tokens"][:, None])

    def pair(carry, xs):
        x, conv, ssm, wk, wv = carry
        lp, layer, lam0 = xs
        x, _, conv, ssm = _mamba_step(cfg, lp["mamba"], x, conv, ssm,
                                      layer)
        ap = lp["attn"]
        h = _norm1(cfg, ap, x)
        with jax.named_scope(WINDOW_SCOPE):
            q = _diff_q(cfg, ap, h)
            k, v = _diff_kv(cfg, ap, h)
        with jax.named_scope(KV_WRITE_SCOPE):
            wk = wk.at[layer, at, pos % W].set(k[:, 0])
            wv = wv.at[layer, at, pos % W].set(v[:, 0])
        with jax.named_scope(WINDOW_SCOPE):
            ring = partial(lax.dynamic_index_in_dim, index=layer, axis=0,
                           keepdims=False)
            o = slot_decode_attention(
                q, _kv_heads(cfg, ring(wk)), _kv_heads(cfg, ring(wv)),
                jnp.minimum(pos + 1, W), scale=_scale(cfg))
            out = _diff_out(cfg, ap, o, lam0)
        x = _residual_mlp(cfg, ap, x + out)
        return (x, conv, ssm, wk, wv), None

    P = cfg.n_pairs
    (x, conv, ssm, wk, wv), _ = lax.scan(
        pair, (x, kv["conv"], kv["ssm"], kv["wk"], kv["wv"]),
        (params["pairs"], jnp.arange(P, dtype=jnp.int32), lam_win))
    x, memory, conv, ssm = _mamba_step(cfg, params["mid"]["mamba"], x,
                                       conv, ssm, P)
    ap = params["mid"]["attn"]
    h = _norm1(cfg, ap, x)
    with jax.named_scope(ATTENTION_SCOPE):
        q = _diff_q(cfg, ap, h)
        k, v = _diff_kv(cfg, ap, h)
    phys = page_table[at, pos // ps]
    ck, cv = llama._write_pages(kv["k"], kv["v"], k[:, 0], v[:, 0], 0,
                                phys, pos % ps)
    if decode_attention_path(cfg, kv, mesh) == "pages":
        # eight walks of the live pages: each cross layer's query hangs
        # on the layer before it, so they cannot share one
        from ..ops.paged_attention import paged_attention_rows
        attend = partial(paged_attention_rows, k_pages=ck, v_pages=cv,
                         page_table=page_table, lengths=pos + 1, layer=0,
                         scale=_scale(cfg))
    else:
        attend = partial(slot_decode_attention,
                         k=_gather_rows(cfg, ck, page_table),
                         v=_gather_rows(cfg, cv, page_table),
                         lengths=pos + 1, scale=_scale(cfg))
    with jax.named_scope(ATTENTION_SCOPE):
        out = _diff_out(cfg, ap, attend(q), lam_full)
    x = _residual_mlp(cfg, ap, x + out)
    x = _cross_decoder(cfg, params, x, memory, attend)
    logits = _final(cfg, params, x)[:, 0]

    new_rngs, sampled = llama._sample_slots(
        sv["rngs"], logits, temperature, top_k, top_p, mesh)
    new_kv = {"k": ck, "v": cv, "wk": wk, "wv": wv, "conv": conv,
              "ssm": ssm}
    return sampled, new_kv, {"lengths": lengths + active.astype(jnp.int32),
                             "tokens": sampled, "rngs": new_rngs}


@jax.named_scope(KV_WRITE_SCOPE)
def _seat_state(kv, state, k, v, pages_row, slot):
    """A prefilled prompt's state into ``slot``: ALL of the slot's
    rings and recurrent state (whatever request held it before), and
    the full layer's keys and values into the slot's pages."""
    ps = kv["k"].shape[2]
    pad = -k.shape[1] % ps
    out = dict(kv)
    for name, a in (("k", k), ("v", v)):
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        pages = a.reshape(-1, ps, a.shape[-1])           # b is 1
        out[name] = kv[name].at[0, pages_row[:pages.shape[0]]].set(
            pages.astype(kv[name].dtype))
    for name in ("wk", "wv", "conv", "ssm"):
        out[name] = lax.dynamic_update_index_in_dim(
            kv[name], state[name][:, 0].astype(kv[name].dtype), slot, 1)
    return out


def _seat_first(cfg, logits, k, v, state, true_len, pages_row, slot, kv,
                sv, rng, temperature, top_k, top_p):
    """The end of an admission: sample the first token from the last
    position's ``logits``, seat the prompt's state into ``slot``, set
    the slot's length, token and rng chain. Returns (first token (1,),
    new kv, new sv)."""
    rng, sub = jax.random.split(rng)
    tok = llama.sample_logits(sub, logits, temperature=temperature,
                              top_k=top_k, top_p=top_p)
    new_kv = _seat_state(kv, state, k, v, pages_row, slot)
    z = jnp.zeros((), jnp.int32)
    new_sv = {
        "lengths": lax.dynamic_update_slice(
            sv["lengths"].astype(jnp.int32), true_len[None], (slot,)),
        "tokens": lax.dynamic_update_slice(
            sv["tokens"], tok.astype(sv["tokens"].dtype), (slot,)),
        "rngs": lax.dynamic_update_slice(
            sv["rngs"], rng[None].astype(sv["rngs"].dtype), (slot, z))}
    return tok, new_kv, new_sv


def prefill_slot_paged(cfg: SambaYConfig, params, tokens, true_len,
                       prefix_len, pages_row, slot, kv, sv, rng,
                       temperature, top_k, top_p, mesh=None):
    """Admission: ``llama.prefill_slot_paged``'s contract, cold only
    (``prefix_len`` is 0: the engine refuses a prefix cache for this
    family). The self-decoder runs over the prompt (END-padded to its
    bucket; the padding moves no state), the cross-decoder on the last
    position alone; the slot's state is overwritten whole. Returns
    (first token (1,), new kv, new sv)."""
    del prefix_len, mesh
    true_len = jnp.asarray(true_len, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    logits, k, v, state = prefill_logits(cfg, params, tokens, true_len)
    return _seat_first(cfg, logits, k, v, state, true_len, pages_row, slot,
                       kv, sv, rng, temperature, top_k, top_p)


# -- a prompt in chunks: the stall a running request sees is one chunk's ----
def init_prefill_stage(cfg: SambaYConfig, capacity: int, chunk: int):
    """Where a prompt that is prefilled ``chunk`` tokens at a time keeps
    what its chunks hand on, outside the slot bank (a decode step in
    between computes on every slot, seated or not): the full layer's
    keys and values of up to ``capacity`` positions, and one slot's
    worth of rings and recurrent state. ``prefill_slot_paged_last``
    seats it."""
    W = cfg.sliding_window
    if chunk % W or capacity % chunk:
        raise ValueError(
            f"a prefill chunk ({chunk}) is a multiple of the sliding "
            f"window ({W}) and divides the slot's capacity ({capacity})")
    one = init_paged_cache(cfg, 1, 1, capacity)
    return {n: (one[n][0] if n in ("k", "v") else one[n])
            for n in STATE_KINDS}


def prefill_slot_paged_chunk(cfg: SambaYConfig, params, tokens, start,
                             stage, mesh=None):
    """One whole chunk of a prompt that is not its last: the
    self-decoder over tokens (1, chunk) at positions ``start ..``, from
    the stage as the chunks before left it (``start`` 0: from nothing)
    to the stage the next chunk goes on from. The slot bank is not
    touched."""
    del mesh
    carry = dict(stage, start=jnp.asarray(start, jnp.int32))
    _, _, k, v, state = _self_decoder(
        cfg, params, llama._embed(cfg, params, tokens), tokens.shape[1],
        carry)
    return {n: a.astype(stage[n].dtype)
            for n, a in dict(state, k=k, v=v).items()}


def prefill_slot_paged_last(cfg: SambaYConfig, params, tokens, start,
                            n_valid, stage, pages_row, slot, kv, sv, rng,
                            temperature, top_k, top_p, mesh=None):
    """A prompt's last chunk, ``n_valid`` tokens END-padded to tokens
    (1, chunk), at positions ``start ..``: the self-decoder over the
    chunk, the cross-decoder on the prompt's last position alone (its
    queries over the stage's keys and values, the whole prompt's), then
    the admission's end as ``prefill_slot_paged``'s: the state seated
    into ``slot`` whole, the first token sampled. Returns (first token
    (1,), new kv, new sv)."""
    del mesh
    start = jnp.asarray(start, jnp.int32)
    logits, k, v, state = prefill_logits(
        cfg, params, tokens, n_valid, carry=dict(stage, start=start))
    return _seat_first(cfg, logits, k, v, state, start + n_valid,
                       pages_row, jnp.asarray(slot, jnp.int32), kv, sv, rng,
                       temperature, top_k, top_p)
