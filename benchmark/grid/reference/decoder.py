"""The plain reference: a pre-norm decoder (RMSNorm, rotary
embedding in the rotate-half convention, grouped-query causal
attention, SwiGLU, untied head) and its mean next-token
cross-entropy, as published for Mistral-7B — in plain ``jax.numpy``,
float32, every matmul at ``highest`` precision, with no cache, no
kernel and no batching, importing nothing from the program.

It reads the weights the system stores (``tok_embed``, ``layers/*``
stacked on a leading layer axis, ``final_norm``, ``lm_head``) and
upcasts one layer at a time, so the weights of a 7.5 GB model are
never held twice. Departures from the published forward pass: none in
the mathematics; attention is computed a block of queries at a time
to bound the score matrix, which changes no value.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(_F32)


def _rope(x, theta):
    """x: (s, heads, hd); rotate-half."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=_F32) / hd)
    ang = jnp.outer(jnp.arange(s, dtype=_F32), inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "theta",
                                   "qblock"))
def _layer(x, layers, i, *, n_heads, n_kv, eps, theta, qblock):
    """Layer ``i`` of the stack on one sequence x: (s, dim) float32."""
    lp = {k: lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
          .astype(_F32) for k, v in layers.items()}
    s, _ = x.shape
    hd = lp["wq"].shape[1] // n_heads
    h = _rms(x, lp["attn_norm"], eps)
    q = jnp.matmul(h, lp["wq"], precision=_HI).reshape(s, n_heads, hd)
    k = jnp.matmul(h, lp["wk"], precision=_HI).reshape(s, n_kv, hd)
    v = jnp.matmul(h, lp["wv"], precision=_HI).reshape(s, n_kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for q0 in range(0, s, qblock):
        qb = q[q0:q0 + qblock]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=_HI) \
            / jnp.sqrt(_F32(hd))
        keep = (jnp.arange(s)[None, :]
                <= (q0 + jnp.arange(qb.shape[0]))[:, None])
        sc = jnp.where(keep[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                               v, precision=_HI))
    o = jnp.concatenate(outs, 0).reshape(s, n_heads * hd)
    x = x + jnp.matmul(o, lp["wo"], precision=_HI)
    h = _rms(x, lp["ffn_norm"], eps)
    gate = jax.nn.silu(jnp.matmul(h, lp["w_gate"], precision=_HI))
    up = jnp.matmul(h, lp["w_up"], precision=_HI)
    return x + jnp.matmul(gate * up, lp["w_down"], precision=_HI)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    return jnp.matmul(_rms(x, final_norm, eps), lm_head.astype(_F32),
                      precision=_HI)


def logits(model, params, tokens, qblock=1024):
    """tokens: (s,) ids of ONE sequence -> (s, vocab) float32 logits.
    ``model`` is the configuration file's object (its published
    keys)."""
    kw = dict(n_heads=model["num_attention_heads"],
              n_kv=model["num_key_value_heads"],
              eps=float(model["rms_norm_eps"]),
              theta=float(model["rope_theta"]), qblock=qblock)
    x = params["tok_embed"][tokens].astype(_F32)
    for i in range(model["num_hidden_layers"]):
        x = _layer(x, params["layers"], i, **kw)
    return _head(x, params["final_norm"], params["lm_head"],
                 eps=kw["eps"])


def loss(model, params, tokens):
    """Mean next-token cross-entropy over tokens: (b, s)."""
    total, count = 0.0, 0
    for row in tokens:
        lg = logits(model, params, row)[:-1]
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, row[1:, None], -1)[:, 0]
        total, count = total + nll.sum(), count + nll.size
    return total / count


def argmax_gaps(model, params, prompt, emitted, pad_to):
    """For a request the system answered greedily: how far below the
    reference's largest logit each emitted token's logit lies, at its
    own position, given the prompt and the tokens emitted before it.
    0 where the system took the reference's argmax. One forward pass
    over prompt + emitted, end-padded to ``pad_to`` (causal, so the
    padding changes nothing before it)."""
    seq = list(prompt) + list(emitted)
    n0, n1 = len(prompt), len(seq)
    toks = jnp.asarray(seq + [0] * (pad_to - n1), jnp.int32)
    lg = logits(model, params, toks)[n0 - 1:n1 - 1]
    took = jnp.take_along_axis(
        lg, jnp.asarray(emitted, jnp.int32)[:, None], -1)[:, 0]
    return lg.max(-1) - took
