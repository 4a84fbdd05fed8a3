"""The plain reference for the SambaY family (Phi-4-mini-flash-
reasoning, arXiv 2507.06607): every layer on every position, in plain
``jax.numpy``, float32, every matmul at ``highest`` precision, with no
cache, no kernel, no batching and no chunked or parallel scan (the
recurrence is one ``lax.scan`` over time), importing nothing from the
program. Differential attention is computed as the paper writes it:
two softmaxes per pair of heads, subtracted.

It reads the weights the system stores (``tok_embed``, ``pairs``,
``mid``, ``cross``: layers of one kind stacked on a leading axis) and
upcasts one layer at a time. Attention is computed a block of queries
at a time and the head a block of the vocabulary at a time, on the
positions asked for only, to bound the temporaries; neither changes a
value.

Where this stands on the paper (and on Mamba's and the Differential
Transformer's papers) and NOT on the published ``config.json``, which
has no key for any of it — the configuration file lists the same under
``assumed``:

- ``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` =
  ceil(hidden_size / 16) = 160: Mamba's defaults (read here from the
  weights' shapes);
- the Mamba mixer itself (arXiv 2312.00752, section 3 and algorithm 2):
  in-projection to (u, z), causal depthwise convolution with bias, SiLU,
  ``x_proj`` to (dt's low-rank input, B, C), ``dt = softplus(W_dt r +
  b_dt)``, ``A = -exp(A_log)``, zero-order hold ``exp(dt A)`` on the
  state and ``dt u B`` on the input, ``y = s C + D u``, output gate
  ``y silu(z)``, out-projection;
- LayerNorm with weight AND bias, ``layer_norm_eps``; no positional
  encoding anywhere (2507.06607, section 2: NoPE);
- the layer pattern: with ``mb_per_layer`` 2 every even layer of the
  self-decoder (layers 0 .. L/2+1) is Mamba, every odd one attention
  with ``sliding_window``, except the last (L/2+1), which is full
  attention; in the cross-decoder even layers are gated memory units and
  odd ones cross attention with queries of their own over layer
  L/2+1's keys and values (2507.06607, section 2 and figure 1);
- the memory every GMU reads is the scan output ``y`` of the LAST Mamba
  layer of the self-decoder (L/2), before its output gate; a GMU is
  ``W_2 (silu(W_1 h) * m)`` (2507.06607, section 2.1);
- the window: query t sees keys ``(t - sliding_window, t]``, itself
  among them;
- differential attention (arXiv 2410.05258, section 2.1; SambaY+DA in
  2507.06607): heads pair up (2i, 2i+1), KV heads (2j, 2j+1); ``a =
  softmax(q1 k1' / sqrt(hd)) v - lam softmax(q2 k2' / sqrt(hd)) v``
  with ``v = [v_2j; v_2j+1]``; ``lam = exp(lq1.lk1) - exp(lq2.lk2) +
  lam_init``; ``lam_init = 0.8 - 0.6 exp(-0.3 l)`` at 0-based depth
  ``l``; then RMSNorm over the double head (with weight, eps as the
  LayerNorm's) times ``1 - lam_init``;
- the MLP is SwiGLU with gate and up in one matrix, gate first.
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


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _mlp(lp, x, eps):
    h = mm(_ln(x, lp["norm2_w"], lp["norm2_b"], eps), lp["w_gate_up"])
    gate, up = jnp.split(h, 2, axis=-1)
    return x + mm(jax.nn.silu(gate) * up, lp["w_down"])


def selective_scan(dt, u, A, B, C, held=_F32):
    """The recurrence itself, one ``lax.scan`` over time from an empty
    state. dt, u: (s, d); A: (d, n); B, C: (s, n). Returns (the state
    after the last step (d, n), y (s, d) without the ``D u`` term).
    ``held`` is the type the state is held in between steps: float32;
    a lower one is the control ``check.scan_tol`` is set against
    (rounded by ``lax.reduce_precision``: a pair of casts the TPU
    compiler is free to take out, and does)."""
    bits = jnp.finfo(held)

    def step(state, xs):
        dt_t, u_t, b_t, c_t = xs
        state = jnp.exp(dt_t[:, None] * A) * state \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        state = lax.reduce_precision(state, bits.nexp, bits.nmant)
        return state, (state * c_t[None, :]).sum(-1)

    return lax.scan(step, jnp.zeros(A.shape, _F32), (dt, u, B, C))


def _mamba(lp, x, eps):
    """One Mamba layer on x (s, dim): (x after the layer, the scan's
    output y before the gate)."""
    s = x.shape[0]
    di, n = lp["A_log"].shape
    k = lp["conv_w"].shape[1]
    r = lp["dt_proj"].shape[0]
    u, z = jnp.split(mm(_ln(x, lp["norm1_w"], lp["norm1_b"], eps),
                        lp["in_proj"]), 2, axis=-1)
    up = jnp.concatenate([jnp.zeros((k - 1, di), _F32), u])
    u = jax.nn.silu(sum(up[j:j + s] * lp["conv_w"][:, j]
                        for j in range(k)) + lp["conv_b"])
    dbl = mm(u, lp["x_proj"])
    dt = jax.nn.softplus(mm(dbl[:, :r], lp["dt_proj"]) + lp["dt_bias"])
    B, C = dbl[:, r:r + n], dbl[:, r + n:]
    _, y = selective_scan(dt, u, -jnp.exp(lp["A_log"]), B, C)
    y = y + lp["D"] * u
    x = x + mm(y * jax.nn.silu(z), lp["out_proj"])
    return _mlp(lp, x, eps), y


def _diff_attention(lp, q, k, v, lam_init, eps, window, qblock):
    """q: (s, H, hd); k, v: (s, Hkv, hd); causal, and within
    ``window`` if it is not None. Returns (s, H * hd)."""
    s, H, hd = q.shape
    Hkv = k.shape[1]
    rep = (H // 2) // (Hkv // 2)
    lam = (jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"]))
           - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + lam_init)
    q1, q2 = q[:, 0::2], q[:, 1::2]                      # (s, H/2, hd)
    k1 = jnp.repeat(k[:, 0::2], rep, axis=1)             # (s, H/2, hd)
    k2 = jnp.repeat(k[:, 1::2], rep, axis=1)
    vv = jnp.repeat(v.reshape(s, Hkv // 2, 2 * hd), rep, axis=1)
    kpos = jnp.arange(s)[None, :]
    outs = []
    for q0 in range(0, s, qblock):
        qpos = (q0 + jnp.arange(min(qblock, s - q0)))[:, None]
        keep = kpos <= qpos
        if window is not None:
            keep = keep & (kpos > qpos - window)

        def soft(qq, kk):
            sc = jnp.einsum("qhd,khd->hqk", qq[q0:q0 + qblock], kk,
                            precision=_HI) / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), -1)
        a = jnp.einsum("hqk,khe->qhe",
                       soft(q1, k1) - lam * soft(q2, k2), vv,
                       precision=_HI)                    # (q, H/2, 2hd)
        outs.append(a)
    a = jnp.concatenate(outs, 0)
    a = a / jnp.sqrt((a * a).mean(-1, keepdims=True) + eps) \
        * lp["subln_w"] * (1.0 - lam_init)
    return a.reshape(s, H * hd)


def _attn(lp, x, kv, lam_init, *, eps, n_heads, n_kv, window, qblock):
    """An attention layer on x (s, dim). ``kv`` None: keys and values
    of its own (returned); else the (k, v) of the full layer."""
    s = x.shape[0]
    h = _ln(x, lp["norm1_w"], lp["norm1_b"], eps)
    hd = lp["wq"].shape[1] // n_heads
    q = mm(h, lp["wq"]).reshape(s, n_heads, hd)
    if kv is None:
        kv = (mm(h, lp["wk"]).reshape(s, n_kv, hd),
              mm(h, lp["wv"]).reshape(s, n_kv, hd))
    a = _diff_attention(lp, q, kv[0], kv[1], lam_init, eps, window, qblock)
    return _mlp(lp, x + mm(a, lp["wo"]), eps), kv


def _gmu(lp, x, memory, eps):
    h = _ln(x, lp["norm1_w"], lp["norm1_b"], eps)
    x = x + mm(jax.nn.silu(mm(h, lp["in_proj"])) * memory, lp["out_proj"])
    return _mlp(lp, x, eps)


def _layer_params(stack, i):
    """Layer ``i`` of a stack (or the stack itself where ``i`` is
    None), upcast to float32."""
    return {k: (v if i is None else v[i]).astype(_F32)
            for k, v in stack.items()}


@partial(jax.jit, static_argnames=("eps",))
def _run_mamba(stack, i, x, *, eps):
    return _mamba(_layer_params(stack, i), x, eps)


@partial(jax.jit, static_argnames=("eps", "n_heads", "n_kv", "window",
                                   "qblock", "own"))
def _run_attn(stack, i, x, kv, lam_init, *, own, **kw):
    return _attn(_layer_params(stack, i), x, None if own else kv,
                 lam_init, **kw)


@partial(jax.jit, static_argnames=("eps",))
def _run_gmu(stack, i, x, memory, *, eps):
    return _gmu(_layer_params(stack, i), x, memory, eps)


@partial(jax.jit, static_argnames=("eps", "tied"))
def _head_block(x, w, b, head, *, eps, tied):
    h = _ln(x, w.astype(_F32), b.astype(_F32), eps)
    head = head.astype(_F32)
    return mm(h, head.T if tied else head)


def lam_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def hidden(model, params, tokens, qblock=1024):
    """tokens: (s,) ids of ONE sequence -> (s, dim) float32, the
    residual stream after the last layer."""
    eps = float(model["layer_norm_eps"])
    L = model["num_hidden_layers"]
    kw = dict(eps=eps, n_heads=model["num_attention_heads"],
              n_kv=model["num_key_value_heads"], qblock=qblock)
    full = L // 2 + 1                      # the full-attention layer
    x = params["tok_embed"][tokens].astype(_F32)
    none = jnp.zeros((), _F32)
    memory = kv = None
    for layer in range(L):
        if layer <= full:                  # the self-decoder
            at, stack = ((None, params["mid"]) if layer >= full - 1
                         else (layer // 2, params["pairs"]))
            if layer % 2 == 0:
                x, memory = _run_mamba(stack["mamba"], at, x, eps=eps)
            else:
                x, own = _run_attn(
                    stack["attn"], at, x, none, lam_init(layer),
                    own=True, **kw,
                    window=(None if layer == full
                            else model["sliding_window"]))
                kv = own if layer == full else kv
        else:                              # the cross-decoder
            at = (layer - full - 1) // 2
            if layer % 2 == 0:
                x = _run_gmu(params["cross"]["gmu"], at, x, memory,
                             eps=eps)
            else:
                x, _ = _run_attn(params["cross"]["attn"], at, x, kv,
                                 lam_init(layer), own=False,
                                 window=None, **kw)
    return x


def logits(model, params, tokens, rows=None, vblock=32768):
    """tokens: (s,) ids of ONE sequence -> (s, vocab) float32 logits,
    or those of the positions ``rows`` only. ``model`` is the
    configuration file's object (its published keys)."""
    x = hidden(model, params, tokens)
    if rows is not None:
        x = x[rows]
    tied = bool(model["tie_word_embeddings"])
    head = params["tok_embed"] if tied else params["lm_head"]
    V = model["vocab_size"]
    kw = dict(eps=float(model["layer_norm_eps"]), tied=tied)
    out = [_head_block(x, params["final_norm_w"], params["final_norm_b"],
                       head[v0:v0 + vblock] if tied
                       else head[:, v0:v0 + vblock], **kw)
           for v0 in range(0, V, vblock)]
    return jnp.concatenate(out, -1)


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
    lg = logits(model, params, toks, rows=jnp.arange(n0 - 1, n1 - 1))
    took = jnp.take_along_axis(
        lg, jnp.asarray(emitted, jnp.int32)[:, None], -1)[:, 0]
    return lg.max(-1) - took
