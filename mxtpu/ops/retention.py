"""Power retention of degree 2 (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): a gated linear-attention
recurrence whose kernel is ``(q . k)^2``, so that a sequence's whole
cache is one matrix-valued state a KV head, whatever its length.

With ``phi: R^d -> R^F`` the symmetric degree-2 feature map
(:func:`sympow2`: ``phi(a) . phi(b) = (a . b)^2``), a gate ``g_t`` in
(0, 1) a KV head and token, and ``s`` the query's scale::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T            (F x dv, a KV head)
    z_t = g_t z_{t-1} + phi(k_t)                  (F)
    y_t = phi(s q_t)^T S_t / (phi(s q_t)^T z_t + EPS)

which equals the attention form ``y_t = sum_{j<=t} G_tj (s q_t . k_j)^2
v_j / (sum_{j<=t} G_tj (s q_t . k_j)^2 + EPS)``, ``G_tj = exp(sum_{m=j+1
..t} log g_m)``. Two forms of that arithmetic here, both given ``(S, z)``
and handing it back:

- :func:`retention_step` advances every sequence of a batch by one
  token (the serving engine's decode step): decay, a rank-one update,
  the read-out of the KV head's query heads;
- :func:`retention_chunk` runs ``C`` tokens at once (a prefill chunk):
  the attention form inside the chunk (``retention_intra``: a ``C x C``
  score matrix a query head, no ``phi``), the state that came in read
  through ``phi(s Q)`` and decayed to each row, and the state handed
  out, ``phi(K)^T`` times the values decayed to the chunk's end
  (``retention_state``), one KV head at a time so that the temporaries
  are one head's.

The state, the sums of ``log g`` and every sum over keys are float32
whatever the activations' type; the state is STORED in the type it
comes in (``S.dtype``: the family's ``state_dtype``), and the products
that build it name their precision (the decode step's update is
elementwise float32; the chunk's ``phi(K)^T V`` runs at
``Precision.HIGH``, three bf16 passes on a TPU), so that what a slot
holds after a prompt and a few hundred steps is float32's sum and a
lower type shows. The read-outs' operands take the backend's default
precision (one bf16 pass on a TPU: the activations' type).

*The layout of phi on the chip.* ``d (d + 1) / 2`` distinct products
(8256 at d = 128) do not tile: :func:`sympow2` stores ``(d / 2 + 1) x
d`` rows (8320 = 65 x 128 at d = 128), row ``(r, i)`` holding ``c_r a_i
a_(i + r mod d)`` for the rotations ``r = 0 .. d / 2``: every rotation
is one lane-aligned row of d products, built with a lane rotation and no
gather. ``r = 0`` are the squares (weight 1); ``0 < r < d / 2`` hold each
unordered pair once (weight sqrt 2); ``r = d / 2`` holds its d / 2 pairs
twice (weight 1 each: 64 rows more than the count). A zero key moves
nothing and a zero ``log g`` decays nothing: that is how a caller masks
a padded position or a slot that is not running.

*The state on the chip.* ``S`` is stored value-major, ``(.., dv, F)``:
a value's row of F products lies along the lanes, so the rank-one update
is a column of ``v`` against a row of ``phi(k)`` and the read-out
contracts the lanes. :func:`retention_step_bank` is the decode step over
a whole bank ``(L, b, G, dv, F)`` and a layer's index; on a TPU it is ONE
Pallas kernel a layer (:func:`retention_step_path` says which runs, from
backend, shapes and types; nobody sets it) that reads each tile of the
layer's state once, writes it back decayed and updated in place
(``input_output_aliases``), and accumulates the read-out of the tile it
holds: one read and one write of the state a step. XLA's form of the
same step reads the state twice (the read-out's fusion recomputes the
update from the old state; then the in-place update) and writes it once
(v5e, PR 33: 20.6 of a 32.0 ms step). Elsewhere (a CPU, a state not held
in float32) the ``jnp`` form runs. The chunked form is XLA's.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["sympow2", "sympow2_rows", "retention_step", "retention_chunk",
           "retention_step_bank", "retention_step_path", "EPS",
           "STATE_SCOPE", "INTRA_SCOPE", "GATE_SCOPE", "STEP_KERNEL_NAME"]

# the named scopes of a retention layer (top level of the layer, so an
# operation's scope is the first name of its path)
STATE_SCOPE = "retention_state"
INTRA_SCOPE = "retention_intra"
GATE_SCOPE = "retention_gate"

# the normaliser's guard, ``y = num / (den + EPS)``: one value, the
# plain reference's (``benchmark/grid/reference/retention.py``), and no
# caller's to set
EPS = 1e-6

_F32 = jnp.float32
_HIGH = lax.Precision.HIGH


def sympow2_rows(d: int) -> int:
    """Rows :func:`sympow2` stores for vectors of ``d`` (even) values:
    ``(d / 2 + 1) d``, against ``d (d + 1) / 2`` distinct products."""
    if d % 2:
        raise ValueError(f"the rotations pair up an even width, got {d}")
    return (d // 2 + 1) * d


def sympow2(a):
    """The symmetric degree-2 feature map: a (.., d) -> (.., (d / 2 + 1)
    d) float32 with ``sympow2(a) . sympow2(b) = (a . b)^2`` (the module
    docstring has the layout)."""
    d = a.shape[-1]
    half = sympow2_rows(d) // d - 1
    a = a.astype(_F32)
    twice = jnp.concatenate([a, a[..., :half]], axis=-1)
    turned = jnp.stack([twice[..., r:r + d] for r in range(half + 1)],
                       axis=-2)                         # (.., half + 1, d)
    weight = jnp.full((half + 1, 1), math.sqrt(2.0), _F32)
    weight = weight.at[0].set(1.0).at[half].set(1.0)
    out = a[..., None, :] * turned * weight
    return out.reshape(a.shape[:-1] + ((half + 1) * d,))


def _step_features(q, k, log_g, scale):
    """What a step reads its state through: g (b, G); phi(k) (b, G, F);
    phi(s q) (b, G, R, F), a KV head's R query heads together; float32."""
    b, H, _ = q.shape
    G = k.shape[1]
    pq = sympow2(q.astype(_F32) * scale).reshape(b, G, H // G, -1)
    return jnp.exp(log_g.astype(_F32)), sympow2(k), pq


def retention_step(q, k, v, log_g, S, z, *, scale: float):
    """One token for every row of a batch. q: (b, H, d); k: (b, G, d);
    v: (b, G, dv); log_g: (b, G) float32; S: (b, G, dv, F) and z: (b, G,
    F), the state before the token, in the type it is held in. Query
    head ``i`` reads KV head ``i // (H / G)``. Returns (y (b, H, dv)
    float32, S, z after the token). A row with ``k = 0`` and ``log_g =
    0`` keeps its state."""
    held = S.dtype
    with jax.named_scope(STATE_SCOPE):
        g, pk, pq = _step_features(q, k, log_g, scale)
        S = (g[..., None, None] * S.astype(_F32)
             + v.astype(_F32)[..., None] * pk[..., None, :]).astype(held)
        z = (g[..., None] * z.astype(_F32) + pk).astype(held)
        num = jnp.einsum("bgrf,bgvf->bgrv", pq, S.astype(_F32))
        den = jnp.einsum("bgrf,bgf->bgr", pq, z.astype(_F32))
        y = num / (den[..., None] + EPS)
    return y.reshape(q.shape[0], q.shape[1], -1), S, z


# -- the decode step over a bank: one read and one write of the state -----------
STEP_KERNEL_NAME = "retention_step_state"
# lanes of a state tile the kernel holds at once: (dv, tile) float32 in
# and out, twice buffered (3.4 MB at dv = 128), under a v5e's default
# 16 MB of scoped VMEM
_TILE_LANES = 2048


def retention_step_path(state_shape, state_dtype) -> str:
    """Which form :func:`retention_step_bank` runs over a bank ``S`` of
    this shape and type on this backend: ``"kernel"`` (the Pallas
    kernel: a TPU, a float32 state whose tiles are whole (8, 128)
    vregs) or ``"jnp"``."""
    dv, F = state_shape[-2:]
    if (jax.default_backend() == "tpu"
            and jnp.dtype(state_dtype) == _F32
            and dv % 8 == 0 and F % 128 == 0):
        return "kernel"
    return "jnp"


def _tile(F: int) -> int:
    """The largest whole number of lane tiles, at most ``_TILE_LANES``
    lanes, that divides F (8320 -> 1664); F itself where F is no
    multiple of 128 (interpret mode at toy widths)."""
    if F % 128:
        return F
    n = F // 128
    return 128 * max(t for t in range(1, _TILE_LANES // 128 + 1)
                     if n % t == 0)


def _step_kernel(layer_ref, gate_ref, v_ref, p_ref, s_ref, y_ref, o_ref, *,
                 operand, precision):
    """One (slot, KV head, tile of F lanes) of the step. gate_ref, v_ref:
    (dv, 1) columns; p_ref: (1 + R, tile), row 0 ``phi(k)`` and rows 1..
    ``phi(s q)`` of the head's R query heads; s_ref -> o_ref: the state's
    tile (dv, tile), aliased; y_ref: (dv, 1 + R), the read-out summed
    over the tiles (its column 0 is ``phi(k)``'s and is dropped)."""
    del layer_ref
    rows = p_ref[...]
    new = gate_ref[...] * s_ref[...] + v_ref[...] * rows[0:1, :]
    o_ref[...] = new
    part = lax.dot_general(
        new.astype(operand), rows.astype(operand), (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=_F32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        y_ref[...] = part

    @pl.when(pl.program_id(2) > 0)
    def _():
        y_ref[...] += part


def _step_pallas(pq, pk, v, g, S, layer, *, operand, interpret=False):
    """The kernel over layer ``layer`` of the bank S (L, b, G, dv, F)
    float32. pq: (b, G, R, F); pk: (b, G, F); v: (b, G, dv); g: (b, G),
    all float32. Returns (phi(s q)^T S_new (b, G, R, dv) float32, the
    bank with the layer's state decayed and updated in place)."""
    _, b, G, dv, F = S.shape
    R, tile = pq.shape[2], _tile(F)
    rows = jnp.concatenate([pk[:, :, None], pq], axis=2)     # (b, G, 1+R, F)
    column = lambda a: jnp.broadcast_to(a[..., None], (b, G, dv, 1))
    zero = lambda: jnp.int32(0)
    small = pl.BlockSpec((None, None, dv, 1),
                         lambda i, j, t, layer: (i, j, zero(), zero()))
    state = pl.BlockSpec((None, None, None, dv, tile),
                         lambda i, j, t, layer: (layer[0], i, j, zero(), t))
    # float32 operands (the tests, chip_smoke's float32 pass) multiply
    # at full precision; the activations' bf16 is the MXU's own type
    # (named: Mosaic lowers no ``highest`` product of bf16 operands,
    # which is what a process-wide default would ask for)
    exact = jnp.dtype(operand) == _F32
    y, S = pl.pallas_call(
        partial(_step_kernel, operand=operand,
                precision=lax.Precision.HIGHEST if exact
                else lax.Precision.DEFAULT),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, G, F // tile),
            in_specs=[small, small,
                      pl.BlockSpec((None, None, 1 + R, tile),
                                   lambda i, j, t, layer: (i, j, zero(), t)),
                      state],
            out_specs=[pl.BlockSpec((None, None, dv, 1 + R),
                                    lambda i, j, t, layer:
                                    (i, j, zero(), zero())),
                       state]),
        out_shape=[jax.ShapeDtypeStruct((b, G, dv, 1 + R), _F32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        # operand 4 (after the scalar-prefetched layer) is the bank
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=STEP_KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), column(g[..., None]),
      column(v), rows, S)
    return jnp.swapaxes(y[..., 1:], 2, 3), S


def retention_step_bank(q, k, v, log_g, S, z, layer, *, scale: float,
                        interpret: bool = False):
    """:func:`retention_step` on layer ``layer`` (a traced scalar) of a
    bank: S (L, b, G, dv, F), z (L, b, G, F). Returns (y (b, H, dv)
    float32, the banks with that layer's state after the token). The
    two forms agree to the rounding of their read-out's operands (the
    kernel's are the type of ``q``; the ``jnp`` form's the backend's
    default precision); the state they write is the same float32."""
    layer = jnp.asarray(layer, jnp.int32)
    at = lambda a: lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
    put = lambda a, new: lax.dynamic_update_index_in_dim(a, new, layer, 0)
    with jax.named_scope(STATE_SCOPE):
        if not interpret and retention_step_path(
                S.shape, S.dtype) != "kernel":
            y, Sl, zl = retention_step(q, k, v, log_g, at(S), at(z),
                                       scale=scale)
            return y, put(S, Sl), put(z, zl)
        g, pk, pq = _step_features(q, k, log_g, scale)
        num, S = _step_pallas(pq, pk, v.astype(_F32), g, S, layer,
                              operand=q.dtype, interpret=interpret)
        zl = g[..., None] * at(z).astype(_F32) + pk
        den = jnp.einsum("bgrf,bgf->bgr", pq, zl)
        y = num / (den[..., None] + EPS)
        return (y.reshape(q.shape[0], q.shape[1], -1), S,
                put(z, zl.astype(z.dtype)))


def _chunk_head(q, k, v, log_g, S, z, scale):
    """:func:`retention_chunk` for ONE KV head: q (b, R, C, d) its query
    heads; k (b, C, d); v (b, C, dv); log_g (b, C); S (b, dv, F); z (b,
    F)."""
    C, held = k.shape[1], S.dtype
    # log of the decay from the chunk's start through token t: <= 0
    cum = jnp.cumsum(log_g.astype(_F32), axis=-1)
    with jax.named_scope(INTRA_SCOPE):
        sc = jnp.einsum("brtd,bjd->brtj", q, k,
                        preferred_element_type=_F32) * scale
        seen = jnp.tril(jnp.ones((C, C), bool))
        decay = jnp.exp(jnp.where(seen, cum[:, :, None] - cum[:, None, :],
                                  -jnp.inf))
        w = sc * sc * decay[:, None]                         # (b, R, C, C)
        num = jnp.einsum("brtj,bjv->brtv", w.astype(v.dtype), v,
                         preferred_element_type=_F32)
        den = w.sum(-1)
    with jax.named_scope(STATE_SCOPE):
        S32, z32 = S.astype(_F32), z.astype(_F32)
        pq = sympow2(q.astype(_F32) * scale)                 # (b, R, C, F)
        into = jnp.exp(cum)[:, None]                         # (b, 1, C)
        num = num + into[..., None] * jnp.einsum("brtf,bvf->brtv", pq, S32)
        den = den + into * jnp.einsum("brtf,bf->brt", pq, z32)
        # what the chunk hands on: token j decayed to the chunk's end
        end = cum[:, -1]
        out = jnp.exp(end[:, None] - cum)                    # (b, C)
        pk = sympow2(k)                                      # (b, C, F)
        S = (jnp.exp(end)[:, None, None] * S32 + jnp.einsum(
            "bjv,bjf->bvf", out[..., None] * v.astype(_F32), pk,
            precision=_HIGH)).astype(held)
        z = (jnp.exp(end)[:, None] * z32 + jnp.einsum(
            "bjf,bj->bf", pk, out, precision=_HIGH)).astype(held)
        y = num / (den[..., None] + EPS)
    return y, S, z


def retention_chunk(q, k, v, log_g, S, z, *, scale: float):
    """``C`` tokens of every row of a batch from the state ``(S, z)``.
    q: (b, H, C, d); k: (b, G, C, d); v: (b, G, C, dv); log_g: (b, G, C)
    float32; S: (b, G, dv, F), z: (b, G, F). Returns (y (b, H, C, dv)
    float32, S, z after the chunk's last token). A position with ``k =
    0`` and ``log_g = 0`` (padding) is no key and moves no state; what
    it reads as a query is its caller's to drop. One KV head at a time
    (``lax.map``): the temporaries are one head's ``phi(s Q)`` and
    scores."""
    b, H, C, d = q.shape
    G = k.shape[1]
    by_head = lambda a: jnp.moveaxis(a, 1, 0)
    y, S, z = lax.map(
        lambda xs: _chunk_head(*xs, scale),
        (by_head(q.reshape(b, G, H // G, C, d)), by_head(k), by_head(v),
         by_head(log_g), by_head(S), by_head(z)))
    return (jnp.moveaxis(y, 0, 1).reshape(b, H, C, -1),
            jnp.moveaxis(S, 0, 1), jnp.moveaxis(z, 0, 1))
