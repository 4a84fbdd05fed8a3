"""State-space recurrences with a carried state, and the short causal
convolution in front of them. Two mixers, each in two forms of the same
arithmetic, in float32 whatever the inputs' type; each returns the state
after its last step, which is what lets a prefill hand its sequence on
to decode. A step whose ``dt`` is zero leaves the state as it was
(``exp(0) = 1``, nothing added): that is how a caller masks the padded
tail of a bucket, or a slot that is not running.

*Mamba's S6* (Gu & Dao 2023), per channel ``d`` and state index ``n``::

    s_t = exp(dt_t[d] * A[d, n]) * s_{t-1} + dt_t[d] * u_t[d] * B_t[n]
    y_t[d] = sum_n s_t[d, n] * C_t[n] + D[d] * u_t[d]

:func:`selective_scan_step` advances every sequence of a batch by one
token (the serving engine's decode step), and :func:`selective_scan`
runs a whole sequence from a given state, a ``lax.scan`` over time whose
loop body is a chunk of ``chunk`` steps unrolled: the state stays on the
chip's vector units across a chunk instead of going through memory at
every step, and the temporaries are one chunk's, whatever the length.
No Pallas kernel here: the scan is XLA's. (On the v5e, 4096 steps of
5120 x 16: 4.7 ms in chunks of 8 or 32, 12.5 ms step by step, and
11-84 ms as a ``lax.associative_scan`` over chunks of 32-512: PR 27.)

*Mamba-2's SSD* (Dao & Gu 2024, "Transformers are SSMs"): a scalar decay
per HEAD, and ``B``, ``C`` shared by a group of heads. Per head ``h``
(of ``p`` channels, in group ``g(h)``)::

    S_t = exp(dt_t[h] * A[h]) * S_{t-1} + dt_t[h] * x_t[h] (outer) B_t[g(h)]
    y_t[h] = S_t C_t[g(h)] + D[h] * x_t[h]

with ``S`` of (p, n) a head. :func:`ssd_step` is one token for every
row of a batch (the decode step: read and write the state once);
:func:`ssd_chunked` is the chunked state-space-duality form of a whole
sequence, ``chunk`` positions at a time: inside a chunk the output is a
masked product, ``(C B^T * L) (dt x)`` with ``L[t, u] = exp(cs_t -
cs_u)`` for ``u <= t`` (``cs`` the running sum of ``dt A``), plus the
carried state read out through ``C`` and decayed by ``exp(cs_t)``; the
state then moves to the chunk's end in one product. A token-by-token
scan would stream the (h, p, n) state through memory every step (2 MB at
Nemotron-H's widths); the chunked form touches it once a chunk and
spends its work in matrix products, at ``highest`` precision: the
products are a small share of a prefill, and a state carried over
thousands of positions keeps what bfloat16 products would round away.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["causal_conv1d", "selective_scan", "selective_scan_step",
           "ssd_step", "ssd_chunked"]

_F32 = jnp.float32


def causal_conv1d(u, w, b, tail):
    """Depthwise causal convolution over time. u: (b, s, d) inputs;
    w: (d, k) taps, the last one on the current input; b: (d,);
    tail: (b, k-1, d), the k-1 inputs that came before ``u[:, 0]``
    (zeros at the start of a sequence). Returns (y (b, s, d) float32,
    padded (b, k-1+s, d)): ``padded`` is ``tail`` and ``u`` end to end,
    from which the caller cuts the next tail at the length it means."""
    k = w.shape[1]
    s = u.shape[1]
    padded = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    w32 = w.astype(_F32)
    y = b.astype(_F32)
    for j in range(k):
        y = y + padded[:, j:j + s].astype(_F32) * w32[:, j]
    return y, padded


def selective_scan_step(state, u, dt, A, B, C, D):
    """One token for every row of a batch. state: (b, d, n) float32;
    u, dt: (b, d); A: (d, n); B, C: (b, n); D: (d,). Returns
    (y (b, d) float32 — before any output gate —, new state)."""
    u, dt = u.astype(_F32), dt.astype(_F32)
    decay = jnp.exp(dt[..., None] * A.astype(_F32))
    state = decay * state + (dt * u)[..., None] * B.astype(_F32)[:, None, :]
    y = jnp.einsum("bdn,bn->bd", state, C.astype(_F32)) \
        + D.astype(_F32) * u
    return y, state


def selective_scan(u, dt, A, B, C, D, state, chunk: int = 8):
    """A whole sequence from ``state``: :func:`selective_scan_step`
    over time, ``chunk`` steps to one iteration of the loop. u, dt:
    (b, s, d); A: (d, n); B, C: (b, s, n); D: (d,); state: (b, d, n)
    float32. Returns (y (b, s, d) float32, the state after step s-1)."""
    def step(state, xs):
        u_t, dt_t, b_t, c_t = xs
        y, state = selective_scan_step(state, u_t, dt_t, A, b_t, c_t, D)
        return state, y

    state, y = lax.scan(step, state.astype(_F32),
                        tuple(a.swapaxes(0, 1) for a in (u, dt, B, C)),
                        unroll=max(1, min(chunk, u.shape[1])))
    return y.swapaxes(0, 1), state


def _heads(a, r):
    """A group's (.., g, n) -> its heads' (.., g r, n): head h reads
    group h // r."""
    return jnp.repeat(a, r, axis=-2)


def ssd_step(state, x, dt, A, B, C, D):
    """One token for every row of a batch. state: (b, h, p, n) float32;
    x: (b, h, p); dt: (b, h) (softplus already applied; 0 leaves a row's
    state as it was); A, D: (h,); B, C: (b, g, n). Returns (y (b, h, p)
    float32 with the ``D x`` term, new state). Elementwise products and
    sums: exact float32 on every backend."""
    r = x.shape[1] // B.shape[1]
    x, dt = x.astype(_F32), dt.astype(_F32)
    decay = jnp.exp(dt * A.astype(_F32))
    B, C = (_heads(a.astype(_F32), r)[:, :, None] for a in (B, C))
    state = decay[..., None, None] * state.astype(_F32) \
        + (dt[..., None] * x)[..., None] * B
    y = jnp.sum(state * C, -1) + D.astype(_F32)[:, None] * x
    return y, state


def ssd_chunked(x, dt, A, B, C, D, state, chunk: int = 128):
    """A whole sequence from ``state``, ``chunk`` positions at a time.
    x: (b, s, h, p); dt: (b, s, h) (0 on padding); A, D: (h,); B, C: (b,
    s, g, n); state: (b, h, p, n). ``s`` a multiple of ``chunk``. Returns
    (y (b, s, h, p) float32 with the ``D x`` term, the state after
    position s-1, float32)."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    r, Q = h // g, chunk
    if s % Q:
        raise ValueError(f"{s} positions are not whole chunks of {Q}")
    hi = lax.Precision.HIGHEST
    A = A.astype(_F32)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def body(S, xs):
        xc, dtc, Bc, Cc = (a.astype(_F32) for a in xs)
        cs = jnp.cumsum(dtc * A, axis=1)                    # (b, Q, h)
        diff = cs[:, :, None] - cs[:, None]                 # (b, t, u, h)
        L = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
        G = jnp.einsum("btgn,bugn->btug", Cc, Bc, precision=hi)
        M = L.reshape(b, Q, Q, g, r) * G[..., None]
        xdt = (xc * dtc[..., None]).reshape(b, Q, g, r, p)
        y = jnp.einsum("btugr,bugrp->btgrp", M, xdt, precision=hi)
        # the carried state, read out and decayed to each position
        y = y + jnp.einsum("btgn,bgrpn->btgrp", Cc, S.reshape(b, g, r, p, n),
                           precision=hi) \
            * jnp.exp(cs).reshape(b, Q, g, r)[..., None]
        # and moved to the chunk's end
        last = cs[:, -1]                                    # (b, h)
        tail = jnp.exp(last[:, None] - cs).reshape(b, Q, g, r)[..., None]
        S = jnp.exp(last)[..., None, None] * S + jnp.einsum(
            "bugrp,bugn->bgrpn", xdt * tail, Bc,
            precision=hi).reshape(b, h, p, n)
        return S, y.reshape(b, Q, h, p) + D.astype(_F32)[:, None] * xc

    def chunks(a):
        return a.reshape((b, s // Q, Q) + a.shape[2:]).swapaxes(0, 1)
    state, y = lax.scan(body, state.astype(_F32),
                        tuple(map(chunks, (x, dt, B, C))))
    return y.swapaxes(0, 1).reshape(b, s, h, p), state
