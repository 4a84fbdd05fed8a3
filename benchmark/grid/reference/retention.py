"""The plain reference for the power-retention family
(``mxtpu/models/retention.py``; Brumby-14B-Base, ``model_type:
brumby``): every layer on every position, in plain ``jax.numpy``,
float32, every matmul at ``highest`` precision, with no state, no
feature map, no chunks, no cache, no batching, importing nothing from
the program.

Retention is computed in its ATTENTION form only. With ``h =
RMSNorm(x)``, ``q = RoPE(RMSNorm_head(h W_q))`` (40 heads), ``k =
RoPE(RMSNorm_head(h W_k))``, ``v = h W_v`` (8 heads; query head ``i``
reads KV head ``i // 5``), ``log g_t = logsigmoid(h_t W_g + b_g)`` a KV
head and token, ``s = 1 / sqrt(head_dim)``::

    y_t = sum_{j<=t} G_tj (s q_t . k_j)^2 v_j
          / (sum_{j<=t} G_tj (s q_t . k_j)^2 + eps)
    G_tj = exp(sum_{m=j+1..t} log g_m)

a block of query rows at a time against every key before them, so the
program's recurrence (a matrix-valued state a KV head, read through the
symmetric square ``phi``), its chunked prefill and its decode step are
held against arithmetic they do not share. Then ``x += concat(y) W_o;
x += SwiGLU(RMSNorm(x))``.

It reads the weights the system stores (``tok_embed``, ``layers``
stacked on a leading axis, ``final_norm``, ``lm_head``) and upcasts one
layer, and one block of the SwiGLU's or the head's columns, at a time:
float32 weights of the benchmark's configuration are 17 GB.

What the published ``config.json`` does not give (the configuration
file lists the same under ``assumed``): the kernel's degree 2; the gate
a KV head through ``logsigmoid`` with a bias ``b_g`` (zeros are the
bias-free layer); ``eps`` = 1e-6; the scale ``s``; per-head RMSNorm on
``q`` and ``k`` and rotate-half RoPE, kept from the Qwen3 block Brumby
was initialised from.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
EPS = 1e-6                      # the normaliser's guard
mm = partial(jnp.matmul, precision=_HI)


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (s, heads, hd), position p at row p: rotate column i with
    column i + hd/2 by ``p / theta^(2i/hd)``."""
    s, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=_F32) / hd))
    ang = (jnp.arange(s, dtype=_F32)[:, None] * inv)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def projections(w, x, eps, theta, H, G):
    """One layer's q (s, H, hd), k and v (s, G, hd) and log g (s, G)
    from the stream x (s, dim) entering it; ``w``: the layer's float32
    weights."""
    s, hd = x.shape[0], w["q_norm"].shape[0]
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(_rms(mm(h, w["wq"]).reshape(s, H, hd), w["q_norm"], eps),
              theta)
    k = _rope(_rms(mm(h, w["wk"]).reshape(s, G, hd), w["k_norm"], eps),
              theta)
    v = mm(h, w["wv"]).reshape(s, G, hd)
    return q, k, v, jax.nn.log_sigmoid(mm(h, w["wg"]) + w["bg"])


def attention_form(q, k, v, log_g, qblock):
    """The retention of q (s, H, hd) over k, v (s, G, hd) with gates
    log_g (s, G), as decayed squared-score attention: (s, H, hd)."""
    s, H, hd = q.shape
    rep = H // k.shape[1]
    k, v = (jnp.repeat(a, rep, 1).transpose(1, 0, 2) for a in (k, v))
    cum = jnp.repeat(jnp.cumsum(log_g, 0), rep, 1).T          # (H, s)
    out = []
    for q0 in range(0, s, qblock):
        n = min(q0 + qblock, s)                   # keys this block sees
        sc = mm(q[q0:n].transpose(1, 0, 2),
                k[:, :n].transpose(0, 2, 1)) / math.sqrt(hd)
        seen = jnp.arange(n)[None, :] <= jnp.arange(q0, n)[:, None]
        decay = jnp.exp(jnp.where(
            seen[None], cum[:, q0:n, None] - cum[:, None, :n], -jnp.inf))
        w = sc * sc * decay                                   # (H, qb, n)
        y = mm(w, v[:, :n]) / (w.sum(-1, keepdims=True) + EPS)
        out.append(y.transpose(1, 0, 2))
    return jnp.concatenate(out)


_RETENTION_WEIGHTS = ("attn_norm", "q_norm", "k_norm", "wq", "wk", "wv",
                      "wg", "bg", "wo")


@partial(jax.jit, static_argnames=("H", "G", "qblock"))
def _retention(lp, at, x, eps, theta, *, H, G, qblock):
    """x + retention(RMSNorm(x)) W_o for layer ``at`` of the stack
    ``lp``: x (s, dim) float32."""
    w = {n: lp[n][at].astype(_F32) for n in _RETENTION_WEIGHTS}
    y = attention_form(*projections(w, x, eps, theta, H, G), qblock)
    return x + mm(y.reshape(x.shape[0], -1), w["wo"])


@partial(jax.jit, static_argnames=("fblock",))
def _ffn(lp, at, x, eps, *, fblock):
    """x + SwiGLU(RMSNorm(x)), ``fblock`` of the inner columns upcast
    at a time."""
    h = _rms(x, lp["ffn_norm"][at].astype(_F32), eps)
    gate, up, down = (lp[n][at] for n in ("w_gate", "w_up", "w_down"))
    y = x
    for f0 in range(0, gate.shape[-1], fblock):
        f1 = f0 + fblock
        y = y + mm(jax.nn.silu(mm(h, gate[:, f0:f1].astype(_F32)))
                   * mm(h, up[:, f0:f1].astype(_F32)),
                   down[f0:f1].astype(_F32))
    return y


def layer(model, params, index, x, qblock=512, fblock=4352):
    """Layer ``index`` of the stack on x (s, dim) float32, the residual
    stream entering it -> the stream leaving it."""
    eps = float(model["rms_norm_eps"])
    x = _retention(params["layers"], index, x, eps,
                   float(model["rope_theta"]),
                   H=model["num_attention_heads"],
                   G=model["num_key_value_heads"], qblock=qblock)
    return _ffn(params["layers"], index, x, eps,
                fblock=min(fblock, model["intermediate_size"]))


def hidden(model, params, tokens):
    """tokens: (s,) ids of ONE sequence -> (s, dim) float32, the
    residual stream after the last layer."""
    x = params["tok_embed"][tokens].astype(_F32)
    for index in range(model["num_hidden_layers"]):
        x = layer(model, params, index, x)
    return x


@jax.jit
def _head_block(x, norm_w, head, eps):
    return mm(_rms(x, norm_w.astype(_F32), eps), head.astype(_F32))


def logits(model, params, tokens, rows=None, vblock=32768):
    """tokens: (s,) ids of ONE sequence -> (s, vocab) float32 logits,
    or those of the positions ``rows`` only. ``model`` is the
    configuration file's object (its published keys)."""
    x = hidden(model, params, tokens)
    if rows is not None:
        x = x[rows]
    tied = bool(model["tie_word_embeddings"])
    head = params["tok_embed"].T if tied else params["lm_head"]
    eps = float(model["rms_norm_eps"])
    return jnp.concatenate(
        [_head_block(x, params["final_norm"], head[:, v0:v0 + vblock], eps)
         for v0 in range(0, model["vocab_size"], vblock)], -1)


def argmax_gaps(model, params, prompt, emitted, pad_to, picks=None):
    """For a request the system answered greedily: how far below the
    reference's largest logit each emitted token's logit lies, at its
    own position, given the prompt and the tokens emitted before it.
    0 where the system took the reference's argmax. One forward pass
    over prompt + emitted, end-padded to ``pad_to`` (causal, so the
    padding changes nothing before it). ``picks`` is the driver's list
    for a router's choices: this family has none."""
    del picks
    seq = list(prompt) + list(emitted)
    n0, n1 = len(prompt), len(seq)
    toks = jnp.asarray(seq + [0] * (pad_to - n1), jnp.int32)
    lg = logits(model, params, toks, rows=jnp.arange(n0 - 1, n1 - 1))
    took = jnp.take_along_axis(
        lg, jnp.asarray(emitted, jnp.int32)[:, None], -1)[:, 0]
    return lg.max(-1) - took


@jax.jit
def state_readout(r, k, v, log_g):
    """What a retention state holds after a sequence, read through
    probe queries and without the state: for probes r (P, hd) and ONE KV
    head's keys k (s, hd), values v (s, dv) and gates log_g (s,), the
    sums ``sum_j G_sj (r . k_j)^2 v_j`` (P, dv) and ``sum_j G_sj (r .
    k_j)^2`` (P,) with every token decayed to the sequence's end. The
    program's ``phi(r)^T S`` and ``phi(r) . z`` are held against
    them."""
    r, k, v, log_g = (a.astype(_F32) for a in (r, k, v, log_g))
    after = jnp.cumsum(log_g[::-1])[::-1] - log_g     # sum over m > j
    sc = mm(r, k.T)
    w = sc * sc * jnp.exp(after)[None, :]
    return mm(w, v), w.sum(-1)
