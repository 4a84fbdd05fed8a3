"""The plain reference for the latent-attention, routed-expert family
(``mxtpu/models/latent_moe.py``; kanana-2-30b-a3b-instruct-2601,
``model_type: deepseek_v3``): every layer on every position, in plain
``jax.numpy``, float32, every matmul at ``highest`` precision, with no
cache, no kernel, no batching, importing nothing from the program.

Attention is computed in the DECOMPRESSED form only — per-head keys
``[k_nope; k_rope]`` and values rebuilt from the latent for every
position, a softmax over them — so the program's absorbed decode is held
against arithmetic it does not share. Every expert is applied to every
token and the unchosen ones are weighted zero, so the program's sort,
grouped product and unsort are held against none of their own steps.

It reads the weights the system stores (``tok_embed``, ``dense``,
``moe``: layers of one kind stacked on a leading axis, the experts on a
second) and upcasts one layer, and one block of experts, at a time:
float32 weights of the benchmark's configuration are 20 GB. Attention is
computed a block of queries at a time and the head a block of the
vocabulary at a time, on the positions asked for only; neither changes a
value.

Where this departs from, or adds to, the published ``config.json`` (the
configuration file lists the same under ``assumed``):

- RoPE pairs column i of the 64 rope columns with column i + 32
  (rotate-half). ``rope_interleave: true`` says how a checkpoint orders
  those columns; with weights from a seed either pairing is the same
  model up to a permutation of ``W_q``'s and ``W_kva``'s columns;
- ``rope_scaling`` is null, so there is no ``mscale`` and the softmax
  scale is ``1 / sqrt(qk_head_dim)``;
- ``n_group = topk_group = 1``: the router's group step is the identity
  and is not written;
- the chosen weights are divided by ``sum + 1e-20`` (the published
  implementation's guard) and then scaled by ``routed_scaling_factor``;
- the shared expert is ONE SwiGLU of ``n_shared_experts x
  moe_intermediate_size``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
mm = partial(jnp.matmul, precision=_HI)


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (s, .., hd), position p at row p: rotate column i with column
    i + hd/2 by ``p / theta^(2i/hd)``."""
    s, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=_F32) / hd))
    ang = jnp.arange(s, dtype=_F32)[:, None] * inv
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (hd // 2,))
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _swiglu(x, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


@partial(jax.jit, static_argnames=("H", "nope", "rope", "dv", "qblock"))
def _attention(lp, at, x, eps, theta, *, H, nope, rope, dv, qblock):
    """x + attention(RMSNorm(x)) for layer ``at`` of the stack ``lp``,
    decompressed: x (s, dim) float32."""
    w = {n: a[at].astype(_F32) for n, a in lp.items()
         if n in ("attn_norm", "kv_norm", "wq", "wkva", "wkvb", "wo")}
    s = x.shape[0]
    R = w["kv_norm"].shape[0]
    h = _rms(x, w["attn_norm"], eps)
    q = mm(h, w["wq"]).reshape(s, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kva = mm(h, w["wkva"])
    c = _rms(kva[:, :R], w["kv_norm"], eps)
    k_rope = _rope(kva[:, R:], theta)                    # (s, rope): one
    kv = mm(c, w["wkvb"]).reshape(s, H, nope + dv)       # vector a token
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (s, H, rope))],
        -1).transpose(1, 2, 0)                           # (H, qk, s)
    v = kv[..., nope:].transpose(1, 0, 2)                # (H, s, dv)
    out = []
    for q0 in range(0, s, qblock):
        qb = q[q0:q0 + qblock].transpose(1, 0, 2)        # (H, qb, qk)
        sc = mm(qb, k) / math.sqrt(nope + rope)
        seen = (jnp.arange(s)[None, :]
                <= (q0 + jnp.arange(qb.shape[1]))[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        out.append(mm(p, v).transpose(1, 0, 2).reshape(-1, H * dv))
    return x + mm(jnp.concatenate(out), w["wo"])


@jax.jit
def _dense_ffn(lp, at, x, eps):
    w = {n: lp[n][at].astype(_F32)
         for n in ("ffn_norm", "w_gate", "w_up", "w_down")}
    return x + _swiglu(_rms(x, w["ffn_norm"], eps), w["w_gate"], w["w_up"],
                       w["w_down"])


def route(h, w_router, bias, top_k, renorm, scale):
    """The router on h (s, dim) float32 -> (the chosen experts (s,
    top_k), the weight of every expert (s, E), zero where not
    chosen)."""
    s = jax.nn.sigmoid(mm(h, w_router))
    _, choice = lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, choice, -1)
    if renorm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], choice].set(w * scale)
    return choice, dense


@partial(jax.jit, static_argnames=("top_k", "renorm", "eblock"))
def _expert_ffn(lp, at, x, eps, scale, *, top_k, renorm, eblock):
    """x + sum_i w_i E_i(RMSNorm(x)) + S(RMSNorm(x)) for expert layer
    ``at``: EVERY expert on every token, ``eblock`` experts' weights
    upcast at a time. Returns (x, the router's choice (s, top_k))."""
    f32 = lambda n: lp[n][at].astype(_F32)
    h = _rms(x, f32("ffn_norm"), eps)
    choice, weight = route(h, f32("router"), f32("router_bias"), top_k,
                           renorm, scale)
    E = weight.shape[1]
    y = _swiglu(h, f32("ws_gate"), f32("ws_up"), f32("ws_down"))
    for e0 in range(0, E, eblock):
        blk = {n: lax.dynamic_slice_in_dim(lp[n][at], e0, eblock, 0)
               .astype(_F32) for n in ("w_gate", "w_up", "w_down")}
        out = jax.vmap(partial(_swiglu, h))(
            blk["w_gate"], blk["w_up"], blk["w_down"])   # (eblock, s, dim)
        y = y + (out * weight[:, e0:e0 + eblock].T[:, :, None]).sum(0)
    return x + y, choice


def layer(model, params, index, x, qblock=512, eblock=16, picks=None):
    """Layer ``index`` of the stack on x (s, dim) float32, the residual
    stream entering it -> the stream leaving it. ``picks``: a list that
    gets an expert layer's router choice (s, top_k)."""
    eps = float(model["rms_norm_eps"])
    nd = model["first_k_dense_replace"]
    attn = dict(H=model["num_attention_heads"],
                nope=model["qk_nope_head_dim"],
                rope=model["qk_rope_head_dim"], dv=model["v_head_dim"],
                qblock=qblock)
    theta = float(model["rope_theta"])
    if index < nd:
        x = _attention(params["dense"], index, x, eps, theta, **attn)
        return _dense_ffn(params["dense"], index, x, eps)
    x = _attention(params["moe"], index - nd, x, eps, theta, **attn)
    x, choice = _expert_ffn(
        params["moe"], index - nd, x, eps,
        float(model["routed_scaling_factor"]),
        top_k=model["num_experts_per_tok"],
        renorm=bool(model["norm_topk_prob"]),
        eblock=min(eblock, model["n_routed_experts"]))
    if picks is not None:
        picks.append(choice)
    return x


def hidden(model, params, tokens, picks=None):
    """tokens: (s,) ids of ONE sequence -> (s, dim) float32, the
    residual stream after the last layer. ``picks``: a list that gets
    every expert layer's router choice (s, top_k), in order."""
    x = params["tok_embed"][tokens].astype(_F32)
    for index in range(model["num_hidden_layers"]):
        x = layer(model, params, index, x, picks=picks)
    return x


@jax.jit
def _head_block(x, norm_w, head, eps):
    return mm(_rms(x, norm_w.astype(_F32), eps), head.astype(_F32))


def logits(model, params, tokens, rows=None, vblock=32768, picks=None):
    """tokens: (s,) ids of ONE sequence -> (s, vocab) float32 logits,
    or those of the positions ``rows`` only. ``model`` is the
    configuration file's object (its published keys)."""
    x = hidden(model, params, tokens, picks=picks)
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
    padding changes nothing before it)."""
    seq = list(prompt) + list(emitted)
    n0, n1 = len(prompt), len(seq)
    toks = jnp.asarray(seq + [0] * (pad_to - n1), jnp.int32)
    lg = logits(model, params, toks, rows=jnp.arange(n0 - 1, n1 - 1),
                picks=picks)
    took = jnp.take_along_axis(
        lg, jnp.asarray(emitted, jnp.int32)[:, None], -1)[:, 0]
    return lg.max(-1) - took
