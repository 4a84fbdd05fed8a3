"""The plain reference for the block-diffusion, routed-expert family
(``mxtpu/models/blockdiff_moe.py``; SDAR-30B-A3B-Chat, ``model_type:
sdar_moe``): the layer and the generation loop in plain ``jax.numpy``,
float32, every matmul at ``highest`` precision, with no cache, no
kernel, no batching, importing nothing from the program.

*The layer* is Qwen3-MoE's: ``x += concat(o) W_o`` with ``q =
RoPE(RMSNorm_head(h W_q))``, ``k = RoPE(RMSNorm_head(h W_k))``, ``v = h
W_v``, query head i on KV head ``i // (H / G)``, ``o = softmax(q k^T /
sqrt(hd) + M) v``; then ``x += sum_k w_k SwiGLU_k(h)`` with ``p =
softmax(h W_r)``, the top k by ``p``, ``w = p / sum(p over the k)``.
**The mask** ``M``: position i sees position j iff ``j // B <= i // B``.
Logits at position i predict the token AT i. Every expert is applied to
every token and the unchosen ones are weighted zero (one block of
experts upcast at a time, so it fits), so the program's sort, grouped
product and unsort are held against none of their own steps.

*Generation* (the release's loop, ``generate``): a prompt of P tokens;
the ``P % B`` tokens past its last whole block open the first block
unmasked, beside ``[MASK]``s. Every pass is ONE FULL FORWARD over prompt
+ committed blocks + the current block under ``M`` (nothing is stored,
so there is no commit pass here: the program's commit pass only stores
keys this loop recomputes). At every masked position the candidate and
its confidence (:func:`unmask`); ``low_confidence_static``: the ``B /
steps`` masked positions of largest confidence take their candidates;
``low_confidence_dynamic``: every masked position over the threshold, or
those if fewer pass. A request asking n tokens gets exactly n: the last
block is cut.

Where this departs from, or adds to, the published ``config.json`` (the
configuration file lists the same under ``assumed``): block length,
steps, remasking, threshold and mask id are the release's defaults;
which positions are masked is STATE, not ``token == mask_token_id``, and
the mask id's logit is -inf before the draw; q/k head norms are
Qwen3-MoE's; RoPE pairs column i with column i + hd/2 (rotate-half).
"""
from __future__ import annotations

import itertools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
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


def _swiglu(x, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


@partial(jax.jit, static_argnames=("H", "G", "hd", "block", "qblock"))
def _attention(lp, at, x, eps, theta, *, H, G, hd, block, qblock):
    """x + attention(RMSNorm(x)) for layer ``at`` of the stack ``lp``
    under the block-causal mask: x (s, dim) float32."""
    w = {n: lp[n][at].astype(_F32) for n in (
        "attn_norm", "q_norm", "k_norm", "wq", "wk", "wv", "wo")}
    s = x.shape[0]
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(_rms(mm(h, w["wq"]).reshape(s, H, hd), w["q_norm"], eps),
              theta)
    k = _rope(_rms(mm(h, w["wk"]).reshape(s, G, hd), w["k_norm"], eps),
              theta)
    v = mm(h, w["wv"]).reshape(s, G, hd)
    # query head i reads KV head i // (H / G)
    k = jnp.repeat(k, H // G, axis=1).transpose(1, 2, 0)     # (H, hd, s)
    v = jnp.repeat(v, H // G, axis=1).transpose(1, 0, 2)     # (H, s, hd)
    out = []
    for q0 in range(0, s, qblock):
        qb = q[q0:q0 + qblock].transpose(1, 0, 2)            # (H, qb, hd)
        sc = mm(qb, k) / math.sqrt(hd)
        at_q = q0 + jnp.arange(qb.shape[1])
        seen = (jnp.arange(s)[None, :] // block
                <= at_q[:, None] // block)
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        out.append(mm(p, v).transpose(1, 0, 2).reshape(-1, H * hd))
    return x + mm(jnp.concatenate(out), w["wo"])


def route(h, w_router, top_k, renorm):
    """The router on h (s, dim) float32 -> (the chosen experts (s,
    top_k), the weight of every expert (s, E), zero where not
    chosen)."""
    p = jax.nn.softmax(mm(h, w_router), axis=-1)
    w, choice = lax.top_k(p, top_k)
    if renorm:
        w = w / w.sum(-1, keepdims=True)
    dense = jnp.zeros_like(p).at[
        jnp.arange(p.shape[0])[:, None], choice].set(w)
    return choice, dense


@partial(jax.jit, static_argnames=("top_k", "renorm", "eblock"))
def _expert_ffn(lp, at, x, eps, *, top_k, renorm, eblock):
    """x + sum_i w_i E_i(RMSNorm(x)) for layer ``at``: EVERY expert on
    every token, ``eblock`` experts' weights upcast at a time. Returns
    (x, the router's choice (s, top_k))."""
    f32 = lambda n: lp[n][at].astype(_F32)
    h = _rms(x, f32("ffn_norm"), eps)
    choice, weight = route(h, f32("router"), top_k, renorm)
    y = jnp.zeros_like(x)
    for e0 in range(0, weight.shape[1], eblock):
        blk = {n: lax.dynamic_slice_in_dim(lp[n][at], e0, eblock, 0)
               .astype(_F32) for n in ("w_gate", "w_up", "w_down")}
        out = jax.vmap(partial(_swiglu, h))(
            blk["w_gate"], blk["w_up"], blk["w_down"])   # (eblock, s, dim)
        y = y + (out * weight[:, e0:e0 + eblock].T[:, :, None]).sum(0)
    return x + y, choice


def layer(model, params, index, x, qblock=512, eblock=16, picks=None):
    """Layer ``index`` of the stack on x (s, dim) float32, the residual
    stream entering it (position p at row p) -> the stream leaving it.
    ``picks``: a list that gets the router's choice (s, top_k)."""
    eps = float(model["rms_norm_eps"])
    lp = params["layers"]
    x = _attention(lp, index, x, eps, float(model["rope_theta"]),
                   H=model["num_attention_heads"],
                   G=model["num_key_value_heads"], hd=model["head_dim"],
                   block=model["block_length"], qblock=qblock)
    x, choice = _expert_ffn(lp, index, x, eps,
                            top_k=model["num_experts_per_tok"],
                            renorm=bool(model["norm_topk_prob"]),
                            eblock=min(eblock, model["num_experts"]))
    if picks is not None:
        picks.append(choice)
    return x


def hidden(model, params, tokens, picks=None):
    """tokens: (s,) ids of ONE sequence -> (s, dim) float32, the
    residual stream after the last layer."""
    x = params["tok_embed"][tokens].astype(_F32)
    for index in range(model["num_hidden_layers"]):
        x = layer(model, params, index, x, picks=picks)
    return x


@jax.jit
def _head_block(x, norm_w, head, eps):
    return mm(_rms(x, norm_w.astype(_F32), eps), head.astype(_F32))


def logits(model, params, tokens, rows=None, vblock=32768, picks=None):
    """tokens: (s,) ids of ONE sequence -> (s, vocab) float32 logits
    under the block-causal mask, or those of the positions ``rows``
    only; row i predicts the token AT position i."""
    x = hidden(model, params, jnp.asarray(tokens, jnp.int32), picks=picks)
    if rows is not None:
        x = x[rows]
    tied = bool(model["tie_word_embeddings"])
    head = params["tok_embed"].T if tied else params["lm_head"]
    eps = float(model["rms_norm_eps"])
    return jnp.concatenate(
        [_head_block(x, params["final_norm"], head[:, v0:v0 + vblock], eps)
         for v0 in range(0, model["vocab_size"], vblock)], -1)


# ---------------------------------------------------------------------------
# the decoding loop
# ---------------------------------------------------------------------------
def per_pass(model) -> int:
    """Positions a denoise pass unmasks (``get_num_transfer_tokens``
    with steps dividing the block)."""
    return model["block_length"] // model["denoising_steps"]


def confidence(model, lg, x0=None, temperature=0.0, top_p=1.0):
    """The candidate and its confidence at every row of lg (r, V)
    float32. Greedy (``temperature`` 0): the argmax (the mask id
    excluded) and its softmax probability at temperature 1. Sampled:
    the distribution is ``softmax(lg / temperature)`` cut to its top-p
    nucleus (the smallest set of the largest probabilities whose mass
    reaches ``top_p``, a tie-class kept whole) and renormalised; the
    draw itself is the program's (``x0`` (r,), teacher-forced) and its
    confidence is its probability there, 0 outside the nucleus. Returns
    (x0 (r,), confidence (r,) float32), numpy."""
    lg = np.array(lg, np.float64)
    lg[:, model["mask_token_id"]] = -np.inf
    if not temperature:
        p = np.exp(lg - lg.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        x0 = p.argmax(-1)
        return x0, p[np.arange(len(x0)), x0].astype(np.float32)
    z = lg / temperature
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    x0 = np.asarray(x0)
    conf = np.zeros(len(x0), np.float32)
    for r in range(len(x0)):
        order = np.argsort(-p[r], kind="stable")
        mass = np.cumsum(p[r][order])
        # the smallest prefix whose mass reaches top_p, its last value's
        # tie-class whole
        last = p[r][order[min(int(np.searchsorted(mass, top_p)),
                              len(order) - 1)]]
        keep = p[r] >= last
        if keep[x0[r]]:
            conf[r] = p[r][x0[r]] / p[r][keep].sum()
    return x0, conf


def transfer(model, conf, masked):
    """Which masked positions of ONE block take their candidates: conf
    (B,), masked (B,) bool -> (take (B,) bool, whether the threshold
    decided). Ties go to the lower position."""
    n = per_pass(model)
    c = np.where(masked, np.asarray(conf, np.float32), -np.inf)
    order = np.argsort(-c, kind="stable")
    take = np.zeros(len(c), bool)
    take[order[:n]] = True
    take &= masked
    if model["remasking"] == "low_confidence_dynamic":
        passing = masked & (c > model["confidence_threshold"])
        if passing.sum() >= n:
            return passing, True
    return take, False


def generate(model, params, prompt, n_new, trace=None):
    """The plain loop, greedy: prompt (P,) ids -> the ``n_new`` tokens a
    request gets. Every pass one full forward over prompt + committed
    blocks + the current block. ``trace``: a list that gets, a pass,
    (the block's first position, the tokens fed (B,), masked (B,), the
    block rows' logits (B, V))."""
    B, mask_id = model["block_length"], model["mask_token_id"]
    seq = [int(t) for t in prompt]
    start = len(seq) // B * B
    out = []
    while len(out) < n_new:
        block = seq[start:] + [mask_id] * (B - len(seq[start:]))
        masked = np.arange(B) >= len(seq) - start
        fresh = masked.copy()
        while masked.any():
            fed = np.where(masked, mask_id, block)
            lg = np.asarray(logits(model, params, seq[:start] + fed.tolist(),
                                   rows=jnp.arange(start, start + B)))
            if trace is not None:
                trace.append((start, fed.copy(), masked.copy(), lg))
            x0, conf = confidence(model, lg)
            take, _ = transfer(model, conf, masked)
            block = np.where(take, x0, block).tolist()
            masked &= ~take
        seq = seq[:start] + [int(t) for t in block]
        out += [int(t) for t, f in zip(block, fresh) if f]
        start += B
    return out[:n_new]


def argmax_gaps(model, params, prompt, emitted, tol, notes=None,
                pad_to=None):
    """For a request the system answered greedily: the replay, block by
    block and pass by pass, teacher-forced with the tokens the system
    emitted. At a pass the reference is fed the block as it then stands
    (the positions filled so far hold the EMITTED tokens, the others
    ``[MASK]``), full forward, no cache; the positions it fills are the
    reference's own ``per_pass`` most confident masked ones, and each
    takes the emitted token, whose reference logit lies ``gap`` under
    that position's largest (0 where the system took the reference's
    argmax). The system emits tokens, not the order they were filled in:
    where a gap of the reference's own order passes ``tol`` the block's
    other orders are tried (a block's state is the SET of filled
    positions, at most 2^B forwards), the first whose gaps all hold is
    taken, and ``notes`` counts ``order_retries`` and keeps
    ``order_conf_under``, the most by which a settled order's pick stood
    under the reference's in confidence. The last block's positions past
    the request's count were drawn and cut: they are not held (the
    replay fills them with the reference's own candidates). Returns the
    gaps, one an emitted token, in the order emitted. ``pad_to``: every
    forward runs over the sequence END-padded to this length (the mask
    keeps what follows a block out of it), so that one shape is
    compiled."""
    B, mask_id = model["block_length"], model["mask_token_id"]
    n = per_pass(model)
    seq = [int(t) for t in prompt]
    emitted = [int(t) for t in emitted]
    start, done, gaps = len(seq) // B * B, 0, []
    notes = notes if notes is not None else {}
    notes.setdefault("order_retries", 0)
    notes.setdefault("order_conf_under", 0.0)
    while done < len(emitted):
        head = seq[start:]
        fresh_at = list(range(len(head), B))
        want = emitted[done:done + len(fresh_at)]
        # the positions with a token to hold (the last block's others
        # were drawn and cut from the request)
        held = dict(zip(fresh_at, want))
        memo = {}

        def look(state):
            """(logits (B, V), candidates, confidences) with the
            positions of ``state`` {position: token} filled."""
            key = frozenset(state.items())
            if key not in memo:
                fed = seq[:start] + head + [state.get(i, mask_id)
                                            for i in fresh_at]
                fed += [0] * ((pad_to or 0) - len(fed))
                lg = np.asarray(logits(model, params, fed,
                                       rows=jnp.arange(start, start + B)))
                memo[key] = (lg,) + confidence(model, lg)
            return memo[key]

        def search(state, own):
            """The gaps of the block's remaining passes from ``state``,
            by position; ``own``: the reference's order whatever it
            reads. None where no order holds."""
            left = [i for i in fresh_at if i not in state]
            if not left:
                return {}
            lg, x0, conf = look(state)
            ranked = sorted(left, key=lambda i: (-conf[i], i))
            first = tuple(sorted(ranked[:n]))
            choices = [first] if own else [first] + [
                c for c in itertools.combinations(left, min(n, len(left)))
                if c != first]
            for choice in choices:
                here = {i: float(lg[i].max() - lg[i][held[i]])
                        for i in choice if i in held}
                if not own and any(g > tol for g in here.values()):
                    continue
                rest = search({**state, **{
                    i: held.get(i, int(x0[i])) for i in choice}}, own)
                if rest is None:
                    continue
                if choice != first:
                    notes["order_conf_under"] = max(
                        notes["order_conf_under"],
                        float(max(conf[i] for i in first)
                              - min(conf[i] for i in choice)))
                return {**here, **rest}
            return None

        got = search({}, own=True)
        if any(g > tol for g in got.values()):
            notes["order_retries"] += 1
            got = search({}, own=False) or got
        gaps += [got[i] for i in held]
        seq = seq[:start] + head + want
        done += len(want)
        start += B
    return np.asarray(gaps, np.float32)
