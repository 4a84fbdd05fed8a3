"""Mixture-of-Experts with expert parallelism over the mesh ``ep``
axis — the last SURVEY §2.4 strategy (the reference era shipped MoE
via external frameworks; the TPU-native form is the GShard/Switch
dispatch: token-choice top-k gating, capacity-factored einsum
dispatch/combine, experts sharded over ``ep``, and XLA inserting the
all-to-alls where the token-sharded and expert-sharded worlds meet).

Design notes (TPU-first):
- Everything is STATIC-SHAPED: capacity ``C`` is a Python int at trace
  time, dropped tokens fall out via masks, and the dispatch/combine are
  einsums — no gather/scatter with data-dependent shapes, so the whole
  layer jits and shards like any matmul stack.
- Expert compute is one batched einsum per projection with the expert
  dim sharded ``P("ep")`` — each ep shard runs its E/ep experts at
  full MXU width; the ``(E, C, d)`` dispatched activations are pinned
  to the same layout so the dispatch einsum lowers to an all-to-all
  over ICI rather than a replicated blow-up.
- The SAME function runs unsharded (mesh=None) — that is the ground
  truth the sharded path is tested against (sharding must never change
  the math), and the single-chip serving path.

Reference counterpart: none in-tree (SURVEY §2.4 lists expert
parallelism as the one NEW-era strategy the reference lacked).
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["init_moe_params", "moe_ffn", "moe_ffn_dense",
           "load_balance_loss", "route_sigmoid", "route_softmax",
           "moe_ffn_dropless", "relu2"]

# the named scopes of a routed expert layer (models/latent_moe.py opens
# each at the top level of its layer): the router; the dispatch (sort,
# permute, unpermute, combine); the grouped products; the shared expert
ROUTER_SCOPE = "moe_router"
DISPATCH_SCOPE = "moe_dispatch"
EXPERTS_SCOPE = "moe_experts"
SHARED_SCOPE = "moe_shared"


def relu2(h):
    """The activation of a NON-gated expert (two matrices, up and down:
    Nemotron-H's ``relu2``): ``relu(h)^2``. An expert bank without
    ``w_gate`` is such a bank, everywhere in this file: ``w_up_t`` (E, f,
    d), the up projection stored TRANSPOSED, and ``w_down`` (E, f, d),
    ``down(relu2(up x)) = relu2(x w_up_t^T) w_down``. Both keep the model
    width minor: a v5e lays an (E, d, f) array whose ``f`` is not whole
    lane tiles (Nemotron-H's 1856) out with ``d`` minor, and the grouped
    product, which takes its operand row-major, then copies the whole
    bank first (4.9 GB at that model's widths)."""
    return jnp.square(jax.nn.relu(h))


def init_moe_params(key, dim: int, hidden: int, n_experts: int,
                    dtype=jnp.float32):
    """Gate + SwiGLU expert bank (llama-FFN-shaped experts):
    gate (d, E); w_gate/w_up (E, d, h); w_down (E, h, d)."""
    kg, k1, k2, k3 = jax.random.split(key, 4)

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype) / math.sqrt(fan_in))

    return {
        "gate": init(kg, (dim, n_experts), dim),
        "w_gate": init(k1, (n_experts, dim, hidden), dim),
        "w_up": init(k2, (n_experts, dim, hidden), dim),
        "w_down": init(k3, (n_experts, hidden, dim), hidden),
    }


def _con(mesh: Optional[Mesh], x, *spec):
    if mesh is None:
        return x          # MoE has no ambient-mesh path to fall to
    from .sharding import mcon
    return mcon(mesh, x, *spec)


def _route(params, x, K: int, C: int):
    """Shared router: top-k gating + GShard k-major capacity-slot
    positions. Returns (probs, idx (T,K), gate_vals (T,K),
    pos (T,K) slot position per choice, keep (T,K))."""
    dt = x.dtype
    logits = (x @ params["gate"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    E = probs.shape[-1]
    gate_vals, idx = lax.top_k(probs, K)                    # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    counts = jnp.zeros((E,), jnp.int32)
    poss, keeps = [], []
    for k in range(K):
        onehot = jax.nn.one_hot(idx[:, k], E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot + counts[None]
        pos_t = jnp.take_along_axis(
            pos, idx[:, k][:, None], axis=1)[:, 0]          # (T,)
        poss.append(pos_t)
        keeps.append(pos_t < C)
        counts = counts + onehot.sum(0)
    return probs, idx, gate_vals, jnp.stack(poss, 1), jnp.stack(keeps, 1)


def _experts(params, xin, mesh):
    """SwiGLU expert bank over (E, C, d) dispatched activations (relu²
    up/down where the bank has no ``w_gate``)."""
    dt = xin.dtype
    xin = _con(mesh, xin, "ep", None, None)
    if "w_gate" not in params:
        h = relu2(jnp.einsum("ecd,ehd->ech", xin,
                             params["w_up_t"].astype(dt)))
    else:
        h = jax.nn.silu(jnp.einsum("ecd,edh->ech", xin,
                                   params["w_gate"].astype(dt))) * \
            jnp.einsum("ecd,edh->ech", xin, params["w_up"].astype(dt))
    h = _con(mesh, h, "ep", None, None)
    eout = jnp.einsum("ech,ehd->ecd", h, params["w_down"].astype(dt))
    return _con(mesh, eout, "ep", None, None)


def moe_ffn(params, x, *, top_k: int = 2, capacity_factor: float = 1.25,
            mesh: Optional[Mesh] = None, no_drop: bool = False,
            dispatch: str = "auto"):
    """Token-choice top-k MoE over SwiGLU experts.

    ``x``: (T, d) tokens (flatten batch×seq first; the leading dim may
    be dp/fsdp-sharded). Returns ``(out (T, d), aux)`` where ``aux``
    is the Switch load-balancing loss term (add
    ``moe_aux_weight * aux`` to the training loss; ≈1.0 at uniform
    routing).

    Tokens beyond an expert's capacity ``C = ceil(T·K/E · cf)`` are
    dropped (their expert contribution is zero — the residual stream
    carries them), the standard static-shape TPU trade. ``no_drop``
    sets C = T (worst case: every token on one expert) — exact, but
    the (T, E, C) dispatch goes QUADRATIC in T, so it is only sane for
    tiny T; serving uses :func:`moe_ffn_dense` instead (exact routing,
    linear in T).

    ``dispatch``: how tokens reach their expert's (E, C, d) buffer.
    ``"gather"`` moves them with a gather + scatter-add — zero matmul
    FLOPs, measured 5× faster single-chip, where the ``"einsum"``
    one-hot matmuls cost 2·T·E·C·d FLOPs but partition cleanly over an
    ``ep``-sharded mesh (the GShard form: the dispatch einsum IS the
    all-to-all). ``"auto"`` picks gather unless the mesh really shards
    ``ep``."""
    T, d = x.shape
    E = params["gate"].shape[-1]
    K = top_k
    C = T if no_drop else max(
        1, int(math.ceil(T * K / E * capacity_factor)))
    dt = x.dtype
    if dispatch not in ("auto", "gather", "einsum"):
        raise ValueError(
            f"dispatch={dispatch!r}: use 'auto', 'gather' or 'einsum'")
    if dispatch == "auto":
        ep = 1 if mesh is None else mesh.shape.get("ep", 1)
        dispatch = "einsum" if ep > 1 else "gather"

    probs, idx, gate_vals, pos, keep = _route(params, x, K, C)

    if dispatch == "gather":
        # slot tables with a trash column/row: dropped (and empty)
        # slots point at a zero pad token, so duplicate scatter
        # targets never collide with live assignments
        slot_tok = jnp.full((E, C + 1), T, jnp.int32)
        slot_gate = jnp.zeros((E, C + 1), jnp.float32)
        tids = jnp.arange(T, dtype=jnp.int32)   # match slot_tok: an
        # x64-default arange would be an invalid int64→int32 scatter
        for k in range(K):
            pc = jnp.where(keep[:, k], pos[:, k], C)   # C = trash col
            slot_tok = slot_tok.at[idx[:, k], pc].set(tids)
            slot_gate = slot_gate.at[idx[:, k], pc].set(gate_vals[:, k])
        slot_tok = slot_tok[:, :C]
        slot_gate = slot_gate[:, :C]
        xpad = jnp.concatenate([x, jnp.zeros((1, d), dt)], axis=0)
        xin = xpad[slot_tok]                           # (E, C, d)
        eout = _experts(params, xin, mesh)
        out = jnp.zeros((T + 1, d), dt).at[slot_tok.reshape(-1)].add(
            (eout * slot_gate[..., None].astype(dt)).reshape(-1, d))
        out = out[:T]
    else:
        # GShard one-hot einsum dispatch/combine (mesh-partitionable)
        dmask = jnp.zeros((T, E, C), jnp.float32)
        combine = jnp.zeros((T, E, C), jnp.float32)
        for k in range(K):
            onehot = jax.nn.one_hot(idx[:, k], E, dtype=jnp.float32)
            slot = jax.nn.one_hot(
                jnp.where(keep[:, k], pos[:, k], C), C,
                dtype=jnp.float32)[:, :C]
            contrib = onehot[:, :, None] * slot[:, None, :]
            dmask = dmask + contrib
            combine = combine + contrib * gate_vals[:, k][:, None, None]
        xin = jnp.einsum("tec,td->ecd", dmask.astype(dt), x)
        eout = _experts(params, xin, mesh)
        out = jnp.einsum("tec,ecd->td", combine.astype(dt), eout)
    out = _con(mesh, out, ("dp", "fsdp"), None)

    aux = load_balance_loss(probs, idx[:, 0])
    return out, aux


def moe_ffn_dense(params, x, *, top_k: int = 2,
                  mesh: Optional[Mesh] = None):
    """EXACT dropless MoE — the serving path. Every token runs through
    every expert; the top-k-masked renormalized gate weights combine
    them. Routing is a pure per-token function (independent of batch
    composition, so decode == prefill == forward), memory/compute are
    LINEAR in T — at E/K× the routed path's FLOPs, the price of
    exactness. Returns (out, aux) like :func:`moe_ffn`."""
    T, d = x.shape
    E = params["gate"].shape[-1]
    dt = x.dtype
    logits = (x @ params["gate"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    gate_vals, idx = lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    w = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx].set(gate_vals)

    if "w_gate" not in params:             # relu² up/down experts
        h = relu2(jnp.einsum("td,ehd->teh", x, params["w_up_t"].astype(dt)))
    else:
        h = jax.nn.silu(jnp.einsum("td,edh->teh", x,
                                   params["w_gate"].astype(dt))) * \
            jnp.einsum("td,edh->teh", x, params["w_up"].astype(dt))
    h = _con(mesh, h, ("dp", "fsdp"), "ep", None)
    eout = jnp.einsum("teh,ehd->ted", h, params["w_down"].astype(dt))
    out = jnp.einsum("ted,te->td", eout, w.astype(dt))
    out = _con(mesh, out, ("dp", "fsdp"), None)
    aux = load_balance_loss(probs, idx[:, 0])
    return out, aux


def load_balance_loss(probs, first_choice):
    """Switch-Transformer load-balancing term: E · Σ_e f_e · p̄_e,
    where f_e is the fraction of tokens whose FIRST choice is e and
    p̄_e the mean router probability for e. Equals 1 at uniform
    routing; differentiable through p̄."""
    E = probs.shape[-1]
    f = jnp.mean(jax.nn.one_hot(first_choice, E, dtype=jnp.float32),
                 axis=0)
    pbar = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * pbar)


# ---------------------------------------------------------------------------
# A dropless routed layer for serving: every token reaches the experts
# it chose, whatever the others chose, and an expert nobody chose is
# not read. One code path for a 32-token decode step and a 1024-token
# prefill chunk. ``moe_ffn_dense`` above (every token through every
# expert) is what its tests hold it against.
# ---------------------------------------------------------------------------
@jax.named_scope(ROUTER_SCOPE)
def route_sigmoid(x, w_router, bias, *, top_k: int, renorm: bool = True,
                  scale: float = 1.0):
    """The bias-corrected sigmoid router (DeepSeek-V3's ``noaux_tc``
    with one group): ``s = sigmoid(float32(x) W_r)``; the ``top_k``
    experts by ``s + bias``; their weights ``s`` itself (the bias
    changes who is chosen, not how much they count), divided by their
    sum (+ 1e-20) if ``renorm``, times ``scale``. The product is in
    float32 at ``highest`` precision whatever x's type: a near-tie
    decided in bfloat16 is another choice. x: (T, d); w_router: (d, E);
    bias: (E,). Returns (idx (T, top_k) int32, weights (T, top_k)
    float32)."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renorm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


@jax.named_scope(ROUTER_SCOPE)
def route_softmax(x, w_router, *, top_k: int, renorm: bool = True):
    """The softmax router (Qwen3-MoE's): ``p = softmax(float32(x) W_r)``
    over the experts; the ``top_k`` experts by ``p``; their weights
    ``p`` itself, divided by their sum if ``renorm`` (``norm_topk_prob``).
    :func:`route_sigmoid`'s contract: the product in float32 at
    ``highest`` precision whatever x's type. x: (T, d); w_router: (d,
    E). Returns (idx (T, top_k) int32, weights (T, top_k) float32)."""
    p = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    w, idx = lax.top_k(p, top_k)
    if renorm:
        w = w / w.sum(-1, keepdims=True)
    return idx.astype(jnp.int32), w


# rows of a tile of the grouped product (``megablox.gmm``), and the most
# of the contraction and of the output columns it takes at once: at this
# family's widths a group's WHOLE matrix (2048 x 768, 768 x 2048: 3.1
# MB) is one tile. Timed on a v5e at 192 and 6,144 rows over 128 groups
# against ``lax.ragged_dot`` and narrower tiles (256 output columns: the
# down projection at 0.67 ms for 0.46; PERF.md section 6, PR 31). The
# two widths are a bfloat16 tile's: a wider type takes as many BYTES (a
# float32 group's whole matrix, twice double-buffered, is past the
# chip's VMEM: ``chip_smoke.py``'s float32 pass died of it)
_GMM_TILE = (192, 2048, 2048)
# and the most bytes one tile of a group's matrix may hold: a wider
# matrix (Nemotron-H's 2688 x 1856: 10 MB in bfloat16) is cut, along its
# longer side, into the widest whole lane tiles that divide it and fit.
# Every tile above (3 MB at kanana's and SDAR's widths, either type)
# lies under it
_GMM_TILE_BYTES = 4 << 20


def grouped_matmul_kernel(lhs, rhs, group_sizes, tm: int,
                          interpret: bool = False, transposed: bool = False):
    """``megablox.gmm``, the Pallas grouped matmul that ships with jax:
    it visits the (row tile, group) pairs that hold rows and reads no
    other group's matrix. lhs: (m, k), m a multiple of ``tm``, rows
    sorted by group; rhs: (G, k, n), or (G, n, k) if ``transposed``;
    group_sizes: (G,) int32. Rows past the groups' total come out
    undefined. ``interpret`` runs it in Pallas' interpret mode (the
    tests, on a CPU)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    # a bfloat16 product names its precision: Mosaic refuses one at
    # float32 contract precision, which a process-wide
    # ``jax_default_matmul_precision=highest`` would ask of it
    ambient = (jax.default_matmul_precision("default")
               if lhs.dtype == jnp.bfloat16 else contextlib.nullcontext())
    # and its tile counts are 32-bit: traced under x64 (the tests) the
    # library's own arithmetic hands the kernel a 64-bit scalar, which
    # the TPU compiler does not take
    tk, tn = (2 * t // rhs.dtype.itemsize for t in _GMM_TILE[1:])
    k, n = rhs.shape[2:0:-1] if transposed else rhs.shape[1:]
    tk, tn = _fit_tile(min(tk, k), min(tn, n), (k, n), rhs.dtype.itemsize)
    with ambient, jax.enable_x64(False):
        return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                   tiling=(tm, tk, tn), interpret=interpret,
                   transpose_rhs=transposed)


def _fit_tile(tk: int, tn: int, dims, itemsize: int):
    """(tk, tn) as they are where the tile holds at most
    ``_GMM_TILE_BYTES``; else the longer side cut, to the next narrower
    whole lane tiles that divide its whole dimension, until it does."""
    while tk * tn * itemsize > _GMM_TILE_BYTES:
        side = 0 if tk >= tn else 1
        t, dim = (tk, tn)[side], dims[side]
        cut = next(c for c in range(t - 128, 0, -128) if dim % c == 0)
        tk, tn = (cut, tn) if side == 0 else (tk, cut)
    return tk, tn


def _grouped_dot(lhs, rhs, group_sizes, tm: int, transposed: bool = False):
    """``lhs[rows of group g] @ rhs[g]`` (``rhs[g]^T`` if ``transposed``)
    for every group with rows. On a TPU :func:`grouped_matmul_kernel`;
    elsewhere ``lax.ragged_dot``, XLA's plain lowering of the same
    product, which the kernel is tested against (decided from the
    backend alone, as ``ops.attention._flash_path`` is)."""
    if jax.default_backend() == "tpu":
        return grouped_matmul_kernel(lhs, rhs, group_sizes, tm,
                                     transposed=transposed)
    if transposed:
        rhs = jnp.swapaxes(rhs, 1, 2)
    return lax.ragged_dot(lhs, rhs, group_sizes)


def moe_ffn_dropless(bank, x, idx, weights, *, layer=None, valid=None):
    """``out[t] = sum_k weights[t, k] * E_idx[t, k](x[t])`` over SwiGLU
    experts, dropless: the (token, choice) assignments are sorted by
    expert, each expert runs on its run of rows in ONE grouped product
    a projection (:func:`_grouped_dot`: an expert with no row is not
    read), and the rows go back to their tokens, weighted, and are
    summed.

    bank: ``w_gate``/``w_up`` (E, d, f) and ``w_down`` (E, f, d) — or,
    without ``w_gate``, non-gated experts, ``w_up_t`` and ``w_down``
    (:func:`relu2` says how they are stored) —, or the whole stack's (L,
    E, ..) with ``layer`` (a traced scalar) saying
    which layer's experts to use: the stack is then handed to the
    product as it is stored, as L x E groups of which this layer's have
    rows (a layer's slab cut out of it would be a copy of it). x:
    (T, d); idx, weights: (T, K) from a router. valid: (T,) bool, the
    tokens that are real; the others are assigned nowhere and come out
    zero. Returns (out (T, d) in x's type, the experts' loads (E,)
    int32)."""
    T, d = x.shape
    K = idx.shape[1]
    gated = "w_gate" in bank
    wg, wu, wd = bank.get("w_gate"), bank["w_up" if gated else "w_up_t"], \
        bank["w_down"]
    E = wu.shape[-3]
    # the sorted rows, padded to whole tiles of the grouped product
    tm = min(_GMM_TILE[0], -(-T * K // 8) * 8)
    rows = -(-T * K // tm) * tm
    with jax.named_scope(DISPATCH_SCOPE):
        flat = idx.reshape(T * K)
        if valid is not None:      # past every expert: sorted to the end
            flat = jnp.where(jnp.repeat(valid, K), flat, E)
        flat = jnp.pad(flat, (0, rows - T * K), constant_values=E)
        sorted_e, order = lax.sort_key_val(
            flat, jnp.arange(rows, dtype=jnp.int32))
        sizes = (sorted_e[:, None] == jnp.arange(E, dtype=jnp.int32)
                 ).sum(0, dtype=jnp.int32)
        groups = sizes
        if layer is not None:
            L = wu.shape[0]
            wg, wu, wd = (a if a is None else a.reshape((L * E,) + a.shape[2:])
                          for a in (wg, wu, wd))
            groups = lax.dynamic_update_slice(
                jnp.zeros((L * E,), jnp.int32), sizes,
                (jnp.asarray(layer, jnp.int32) * E,))
        # a padding row reads the last token: it belongs to no group
        xs = x[jnp.minimum(order // K, T - 1)]
    with jax.named_scope(EXPERTS_SCOPE):
        if gated:
            h = jax.nn.silu(_grouped_dot(xs, wg, groups, tm)) \
                * _grouped_dot(xs, wu, groups, tm)
        else:
            h = relu2(_grouped_dot(xs, wu, groups, tm, transposed=True))
        ys = _grouped_dot(h, wd, groups, tm)
    with jax.named_scope(DISPATCH_SCOPE):
        # rows past the last group hold no expert's output
        ys = jnp.where((sorted_e < E)[:, None], ys, 0)
        back = jnp.zeros((rows,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32))
        y = ys[back[:T * K]].reshape(T, K, d).astype(jnp.float32)
        out = (y * weights[..., None]).sum(1).astype(x.dtype)
    return out, sizes
