"""The plain reference for the Nemotron-H family (``mxtpu/models/
nemotron_h.py``; NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type:
nemotron_h``): every layer on every position, in plain ``jax.numpy``,
float32, every matmul at ``highest`` precision, with no cache, no
kernel, no batching and no chunked form of the recurrence (it is one
``lax.scan`` over time, a token at a time), importing nothing from the
program.

Block ``i`` is chosen by character ``i`` of ``hybrid_override_pattern``
and is ``h = h + mixer(RMSNorm(h))``, eps ``norm_eps``; then the final
RMSNorm and the untied ``lm_head``:

- ``M``, Mamba-2 (``transformers``' ``Mamba2Mixer.torch_forward``, read,
  not imported): ``in_proj`` to ``[z (H p) | xBC (H p + 2 G n) | dt
  (H)]``; ``xBC = silu(causal depthwise conv of conv_kernel taps +
  bias)``, split into ``x`` (H heads of p), ``B``, ``C`` (G groups of n;
  head h reads group ``h // (H / G)``); ``dt = softplus(dt + dt_bias)``,
  no clamp; ``A = -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  (outer) B_t``, ``y_t = S_t C_t + D x_t``; ``y = GroupRMSNorm(y *
  silu(z)) w`` over groups of ``H p / G`` channels, after the gate;
  ``out_proj``;
- ``E``, experts: ``s = sigmoid(h W_r)``, the ``num_experts_per_tok``
  experts by ``s + e_score_correction_bias``, weights ``s`` of the
  chosen divided by their sum (+ 1e-20) and times
  ``routed_scaling_factor``; every expert ``down(relu(up h)^2)`` on every
  token, the unchosen weighted zero; plus the shared expert
  ``down_s(relu(up_s h)^2)``. ``n_group = topk_group = 1``: the group
  step is the identity and is not written;
- ``*``, attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` of ``head_dim``, no bias, causal softmax at
  ``1 / sqrt(head_dim)``, NO position encoding (the published code
  applies none; ``rope_theta`` and ``partial_rotary_factor`` are keys it
  does not read).

It reads the weights the system stores (``tok_embed``, ``mamba``,
``moe``, ``attn``: layers of one kind stacked on a leading axis in the
order the pattern meets them, the experts on a second; an expert's up
projection stored transposed, ``w_up_t`` (f, dim)) and upcasts one
layer, and one block of experts, at a time: one expert layer's bank in
float32 is 5.2 GB. Attention is computed a block of queries at a time
and the head a block of the vocabulary at a time, on the positions asked
for only; neither changes a value.
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


def ssm_scan(x, dt, A, B, C, held=_F32):
    """The recurrence a token at a time, from an empty state. x: (s, H,
    p); dt: (s, H); A: (H,); B, C: (s, G, n). Returns (the state after
    the last position (H, p, n), y (s, H, p) without the ``D x`` term).
    ``held`` is the type the state is held in between steps: float32; a
    lower one is the control ``check.state_tol`` is set against
    (``lax.reduce_precision``: a pair of casts the TPU compiler is free
    to take out)."""
    r = x.shape[1] // B.shape[1]
    bits = jnp.finfo(held)
    x, dt, A, B, C = (a.astype(_F32) for a in (x, dt, A, B, C))

    def step(S, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = jnp.repeat(b_t, r, 0), jnp.repeat(c_t, r, 0)   # (H, n)
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[..., None] * b_t[:, None, :]
        S = lax.reduce_precision(S, bits.nexp, bits.nmant)
        return S, (S * c_t[:, None, :]).sum(-1)

    H, p = x.shape[1:]
    return lax.scan(step, jnp.zeros((H, p, B.shape[-1]), _F32),
                    (x, dt, B, C))


def mamba_inputs(w, h, G, n, k):
    """The Mamba-2 mixer in front of the recurrence on h (s, dim), the
    normed stream, with one layer's float32 weights ``w``: (x (s, H, p),
    dt (s, H), B, C (s, G, n), z (s, H p))."""
    s = h.shape[0]
    H = w["A_log"].shape[0]
    proj = mm(h, w["in_proj"])
    conv_dim = w["conv_w"].shape[0]
    Di = conv_dim - 2 * G * n
    z, xbc, dt = jnp.split(proj, [Di, Di + conv_dim], axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, conv_dim), _F32), xbc])
    xbc = jax.nn.silu(sum(padded[j:j + s] * w["conv_w"][:, j]
                          for j in range(k)) + w["conv_b"])
    x = xbc[:, :Di].reshape(s, H, Di // H)
    B = xbc[:, Di:Di + G * n].reshape(s, G, n)
    C = xbc[:, Di + G * n:].reshape(s, G, n)
    return x, jax.nn.softplus(dt + w["dt_bias"]), B, C, z


@partial(jax.jit, static_argnames=("G", "n", "k"))
def _mamba(lp, at, x, eps, *, G, n, k):
    w = {name: a[at].astype(_F32) for name, a in lp.items()}
    s = x.shape[0]
    xs, dt, B, C, z = mamba_inputs(w, _rms(x, w["norm"], eps), G, n, k)
    _, y = ssm_scan(xs, dt, -jnp.exp(w["A_log"]), B, C)
    y = (y + w["D"][:, None] * xs).reshape(s, -1) * jax.nn.silu(z)
    g = y.reshape(s, G, -1)
    g = g / jnp.sqrt((g * g).mean(-1, keepdims=True) + eps)
    return x + mm(g.reshape(s, -1) * w["gnorm"], w["out_proj"])


@partial(jax.jit, static_argnames=("H", "Hkv", "hd", "qblock"))
def _attention(lp, at, x, eps, *, H, Hkv, hd, qblock):
    w = {name: a[at].astype(_F32) for name, a in lp.items()}
    s = x.shape[0]
    h = _rms(x, w["norm"], eps)
    q = mm(h, w["wq"]).reshape(s, H, hd).transpose(1, 0, 2)
    rep = H // Hkv
    k, v = (jnp.repeat(mm(h, w[n]).reshape(s, Hkv, hd), rep, 1)
            for n in ("wk", "wv"))
    k, v = k.transpose(1, 2, 0), v.transpose(1, 0, 2)
    out = []
    for q0 in range(0, s, qblock):
        qb = q[:, q0:q0 + qblock]
        sc = mm(qb, k) / math.sqrt(hd)
        seen = (jnp.arange(s)[None, :]
                <= (q0 + jnp.arange(qb.shape[1]))[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        out.append(mm(p, v).transpose(1, 0, 2).reshape(-1, H * hd))
    return x + mm(jnp.concatenate(out), w["wo"])


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


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
def _experts(lp, at, x, eps, scale, *, top_k, renorm, eblock):
    """x + sum_i w_i E_i(RMSNorm(x)) + S(RMSNorm(x)) for expert layer
    ``at``: EVERY expert on every token, ``eblock`` experts' weights
    upcast at a time, one block after the other (a loop, so that one
    block's temporaries exist at once: beside the engine the chip has
    ~3 GB to spare). Returns (x, the router's choice (s, top_k))."""
    f32 = lambda name: lp[name][at].astype(_F32)
    h = _rms(x, f32("norm"), eps)
    choice, weight = route(h, f32("router"), f32("router_bias"), top_k,
                           renorm, scale)
    E = weight.shape[1]

    def block(i, y):
        e0 = i * eblock
        up, down = (lax.dynamic_slice_in_dim(lp[name][at], e0, eblock, 0)
                    .astype(_F32) for name in ("w_up_t", "w_down"))
        out = jax.vmap(lambda u, d: mm(_relu2(mm(h, u.T)), d))(up, down)
        w = lax.dynamic_slice_in_dim(weight, e0, eblock, 1)
        return y + (out * w.T[:, :, None]).sum(0)
    y = mm(_relu2(mm(h, f32("ws_up"))), f32("ws_down"))
    return x + lax.fori_loop(0, E // eblock, block, y), choice


def layer(model, params, index, x, qblock=512, eblock=8, picks=None):
    """Layer ``index`` of the stack on x (s, dim) float32, the residual
    stream entering it -> the stream leaving it. ``picks``: a list that
    gets an expert layer's router choice (s, top_k)."""
    pattern = model["hybrid_override_pattern"]
    kind = pattern[index]
    at = pattern[:index].count(kind)
    eps = float(model["norm_eps"])
    if kind == "M":
        return _mamba(params["mamba"], at, x, eps, G=model["n_groups"],
                      n=model["ssm_state_size"], k=model["conv_kernel"])
    if kind == "*":
        return _attention(params["attn"], at, x, eps,
                          H=model["num_attention_heads"],
                          Hkv=model["num_key_value_heads"],
                          hd=model["head_dim"], qblock=qblock)
    x, choice = _experts(
        params["moe"], at, x, eps, float(model["routed_scaling_factor"]),
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
    eps = float(model["norm_eps"])
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
