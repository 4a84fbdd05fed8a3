"""Llama-family transformer — the flagship LLM (BASELINE config 5:
"Llama-3-8B ... stress hybridize→HLO at LLM scale").

No reference counterpart exists (MXNet predates Llama; its nearest
artifact is the interleaved-MHA contrib op,
``src/operator/contrib/transformer.cc`` [path cite — unverified]), so
this is a TPU-first design rather than a rebuild:

- **functional core**: pure ``forward(cfg, params, tokens)`` over a
  parameter pytree; composes with ``mxtpu.parallel.step`` for the
  jitted, donated, mesh-sharded train step.
- **scan-over-layers**: per-layer params are stacked on a leading layer
  dim and the block is a ``lax.scan`` — HLO stays O(1) in depth, which
  is what keeps Llama-8B trace/compile time sane (SURVEY.md §7.2.2).
- **remat**: ``jax.checkpoint`` around each layer when
  ``cfg.remat=True`` trades FLOPs for HBM (the reference's
  mirror/memonger had the same role). How much is traded is read off
  the device: at ``remat_policy=None`` a layer keeps, by name, as many
  of its activations as the device's free memory holds
  (:func:`remat_plan`) and the backward pass computes only the rest
  again; a device that reports no memory (the CPU) keeps the layer's
  input alone, as does any trace but ``make_train_step``'s, which
  alone knows the state the device will hold.
- **GQA + RoPE + SwiGLU + RMSNorm**, bf16 activations / f32 params,
  f32 logits for a stable softmax.
- **parallelism-aware**: ``sharding_rules`` gives Megatron-style tp
  sharding + fsdp; activations are sequence-sharded over ``sp`` and the
  attention inner loop can run as ring attention
  (``mxtpu.ops.attention.ring_attention``) under ``shard_map``.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..ops.threshold import thresholds
from ..ops.attention import (flash_attention, dense_attention,
                             ring_attention, ulysses_attention,
                             slot_decode_attention,
                             paged_decode_attention, paged_decode_path,
                             ATTENTION_SCOPE, KV_GATHER_SCOPE,
                             ATTN_OUT_NAME, ATTN_STATS_NAME, _flash_path)
from ..parallel.sharding import (ShardingRules, bytes_per_device,
                                 constrain)
from ..parallel.step import traced_state_bytes
from ..parallel.sharding import mcon as _mcon

__all__ = ["LlamaConfig", "init_params", "forward", "forward_hidden",
           "loss_fn", "chunked_softmax_xent", "sharding_rules",
           "remat_plan", "REMAT_LADDER",
           "CONFIGS", "init_cache", "cache_specs", "prefill",
           "chunked_prefill", "decode_step", "generate",
           "quantize_params_int8", "int8_sharding_rules",
           "sample_logits", "prefill_detached",
           "prefill_detached_chunk", "paged_cache_specs", "init_paged_cache",
           "decode_slots_paged", "prefill_slot_paged",
           "inject_paged_kv", "copy_page", "decode_slots_spec",
           "decode_attention_path"]


@dataclass(frozen=True)
class LlamaConfig:
    family: ClassVar[str] = "llama"  # models.serving_family's key
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336          # SwiGLU inner dim
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32
    attn_impl: str = "flash"         # flash | dense | ring | ulysses
    remat: bool = True
    # None = fit to the device: in ``make_train_step``'s step each layer
    # saves the named activations of ``remat_plan`` that the device's
    # free memory holds and the backward recomputes the rest (full
    # per-layer remat where the device reports no memory, the CPU, and
    # in any other trace); "dots_no_batch" saves
    # weight-matmul outputs and recomputes only elementwise/attention
    # in the backward (MaxText-style "minimal" policy: ~25% less
    # recompute FLOPs for a modest activation-memory increase)
    remat_policy: Optional[str] = None
    scan_layers: bool = True
    tie_embeddings: bool = False
    # cross-entropy vocab chunk: 0 = auto (chunked when the (B,S,V)
    # logits would dominate HBM, i.e. vocab > 16384), None/False =
    # always materialize full logits, int = explicit chunk width
    ce_chunk: Optional[int] = 0
    # Mixture-of-Experts FFN (expert parallelism over the mesh 'ep'
    # axis): 0 = dense FFN; >0 replaces every layer's FFN with that
    # many SwiGLU experts (parallel.moe)
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity: float = 1.25
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


# Named configs; llama3_8b is the BASELINE config-5 target, the small
# ones are for tests/dryrun.
CONFIGS: Dict[str, LlamaConfig] = {
    "tiny": LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                        remat=False),
    "llama3_8b": LlamaConfig(vocab_size=128256, dim=4096, n_layers=32,
                             n_heads=32, n_kv_heads=8, hidden_dim=14336,
                             max_seq_len=8192),
    "llama2_7b": LlamaConfig(vocab_size=32000, dim=4096, n_layers=32,
                             n_heads=32, n_kv_heads=32, hidden_dim=11008,
                             rope_theta=10000.0, max_seq_len=4096),
    # Mixtral-8x7B-class MoE (≈46.7B params, 12.9B active/token):
    # 8 SwiGLU experts per layer, top-2 routing — the expert-parallel
    # flagship config (AOT-gated in bench.py aot_moe)
    "mixtral_8x7b": LlamaConfig(vocab_size=32000, dim=4096,
                                n_layers=32, n_heads=32, n_kv_heads=8,
                                hidden_dim=14336, rope_theta=1e6,
                                max_seq_len=4096, moe_experts=8,
                                moe_top_k=2),
}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_layer(key, cfg: LlamaConfig, n: int):
    """Stacked params for n layers (leading dim = layer index)."""
    hd = cfg.head_dim
    ks = jax.random.split(key, 8)
    d = cfg.param_dtype
    # small-init (scaled by fan-in) — GPT-2/Llama style
    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, d) / math.sqrt(fan_in))
    out = {
        "attn_norm": jnp.ones((n, cfg.dim), d),
        "wq": init(ks[0], (n, cfg.dim, cfg.n_heads * hd), cfg.dim),
        "wk": init(ks[1], (n, cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
        "wv": init(ks[2], (n, cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
        "wo": init(ks[3], (n, cfg.n_heads * hd, cfg.dim),
                   cfg.n_heads * hd * 2 * cfg.n_layers),
        "ffn_norm": jnp.ones((n, cfg.dim), d),
    }
    E = cfg.moe_experts
    if E:
        out["moe_gate"] = init(ks[7], (n, cfg.dim, E), cfg.dim)
        out["w_gate"] = init(ks[4], (n, E, cfg.dim, cfg.hidden_dim),
                             cfg.dim)
        out["w_up"] = init(ks[5], (n, E, cfg.dim, cfg.hidden_dim),
                           cfg.dim)
        out["w_down"] = init(ks[6], (n, E, cfg.hidden_dim, cfg.dim),
                             cfg.hidden_dim * 2 * cfg.n_layers)
    else:
        out["w_gate"] = init(ks[4], (n, cfg.dim, cfg.hidden_dim),
                             cfg.dim)
        out["w_up"] = init(ks[5], (n, cfg.dim, cfg.hidden_dim), cfg.dim)
        out["w_down"] = init(ks[6], (n, cfg.hidden_dim, cfg.dim),
                             cfg.hidden_dim * 2 * cfg.n_layers)
    return out


def init_params(cfg: LlamaConfig, rng: Optional[jax.Array] = None):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    params = {
        "tok_embed": jax.random.normal(
            k_emb, (cfg.vocab_size, cfg.dim), cfg.param_dtype) * 0.02,
        "layers": _init_layer(k_layers, cfg, cfg.n_layers),
        "final_norm": jnp.ones((cfg.dim,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            k_head, (cfg.dim, cfg.vocab_size), cfg.param_dtype) \
            / math.sqrt(cfg.dim)
    return params


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------
def sharding_rules(cfg: Optional[LlamaConfig] = None) -> ShardingRules:
    """Megatron tp + fsdp placement. Layer-stacked params carry a
    leading (unsharded) layer dim. Embedding rows over tp so the
    one-hot matmul psums over tp; lm_head columns over tp (vocab-
    parallel logits). With MoE the expert banks gain a leading E dim
    sharded over ep (expert parallelism) while keeping the same
    fsdp/tp layout per expert.

    TODO(pp): there is deliberately no ``pp`` axis here yet. GPipe
    microbatching exists and is differentiable+tested standalone
    (``parallel/pipeline.py``, test_parallel), but on the ≤8-device
    meshes this repo can measure, fsdp×tp (+sp/ep) dominates a
    pipeline that idles (stages-1)/(stages-1+microbatches) of the
    chips, so the flagship composition is parked until a topology that
    needs it (cross-host meshes where pp's point-to-point beats fsdp's
    all-gather). Owned by the parity-shim row in COMPONENTS.md — keep
    these two in sync when the composition lands."""
    L = None  # leading layer axis of scanned params: never sharded
    moe = bool(cfg and cfg.moe_experts)
    ffn_up = (P(L, "ep", "fsdp", "tp") if moe else P(L, "fsdp", "tp"))
    ffn_dn = (P(L, "ep", "tp", "fsdp") if moe else P(L, "tp", "fsdp"))
    return ShardingRules([
        (r"tok_embed$",        P("tp", "fsdp")),
        (r"layers/w[qkv]$",    P(L, "fsdp", "tp")),   # column parallel
        (r"layers/wo$",        P(L, "tp", "fsdp")),   # row parallel
        (r"layers/moe_gate$",  P()),
        (r"layers/w_(gate|up)$", ffn_up),
        (r"layers/w_down$",    ffn_dn),
        (r"norm",              P()),
        (r"lm_head$",          P("fsdp", "tp")),
        (r".*",                P()),
    ])


# activation specs (sequence sharded over sp)
_ACT = P(("dp", "fsdp"), "sp", None)            # (batch, seq, dim)
_QKV = P(("dp", "fsdp"), "tp", "sp", None)      # (batch, heads, seq, hd)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Every piece of the block runs under a ``jax.named_scope``, spelled
# once, in the helper the block's three forms (_layer, _layer_cached,
# _layer_slots_paged: no cache, contiguous rows, the pool by index)
# share: embed,
# norm, qkv_proj, rope, kv_write, kv_gather, attention, out_proj, mlp,
# lm_head, sampler, xent. The compiled program keeps them in each
# instruction's metadata, and ``telemetry.programs()`` maps a trace's
# operations back to them, so train and serve report under one set of
# names. They do not nest (but for the int8 prefill's read of old pages
# inside kv_write), so an operation's scope is the first of its path.
KV_WRITE_SCOPE = "kv_write"
SAMPLER_SCOPE = "sampler"


@jax.named_scope("norm")
def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * weight.astype(x.dtype)


def rope_tables(cfg: LlamaConfig, seq_len: int, offset: int = 0):
    hd = cfg.head_dim
    inv_freq = 1.0 / (cfg.rope_theta **
                      (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    t = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)                 # (seq, hd/2)
    return jnp.cos(freqs), jnp.sin(freqs)


@jax.named_scope("rope")
def apply_rope(x, cos, sin):
    """x: (b, h, s, hd); rotate-half convention."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("embed")
def _embed(cfg: LlamaConfig, params, tokens):
    """Token rows of the embedding table in cfg.dtype. A weight-only
    int8 table dequantises the GATHERED rows only (its scale is
    per-dim-channel)."""
    emb = params["tok_embed"]
    if isinstance(emb, dict):
        return emb["q8"][tokens].astype(cfg.dtype) * \
            emb["s8"][0].astype(cfg.dtype)
    return emb[tokens].astype(cfg.dtype)


def _qkv(cfg: LlamaConfig, lp, h, cos, sin):
    """The block's q/k/v projections of h (b, s, dim): q (b, n_heads,
    s, hd), k and v (b, n_kv_heads, s, hd), rope on q and k."""
    b, s, _ = h.shape
    hd = cfg.head_dim
    dt = cfg.dtype
    with jax.named_scope("qkv_proj"):
        q = (h @ _wq8(lp["wq"], dt)).reshape(b, s, cfg.n_heads, hd)
        k = (h @ _wq8(lp["wk"], dt)).reshape(b, s, cfg.n_kv_heads, hd)
        v = (h @ _wq8(lp["wv"], dt)).reshape(b, s, cfg.n_kv_heads, hd)
        q = q.transpose(0, 2, 1, 3)          # (b, h, s, hd)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    return (checkpoint_name(apply_rope(q, cos, sin), "attn_q"),
            checkpoint_name(apply_rope(k, cos, sin), "attn_k"),
            checkpoint_name(v, "attn_v"))


@jax.named_scope("out_proj")
def _out_proj(cfg: LlamaConfig, lp, o):
    """Attention's output o (b, n_heads, s, hd) through wo: (b, s,
    dim)."""
    b, _, s, hd = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * hd)
    return o @ _wq8(lp["wo"], cfg.dtype)


@jax.named_scope("lm_head")
def _lm_head(cfg: LlamaConfig, params, x):
    """x (b, s, dim) -> logits (b, s, V) in float32."""
    hw = (_wq8(params["tok_embed"], cfg.dtype).T if cfg.tie_embeddings
          else _wq8(params["lm_head"], cfg.dtype))
    return jnp.einsum("bsd,dv->bsv", x, hw,
                      preferred_element_type=jnp.float32)


@jax.named_scope(ATTENTION_SCOPE)
def _attention(cfg: LlamaConfig, q, k, v, mesh: Optional[Mesh]):
    sp_ok = mesh is not None and "sp" in mesh.axis_names
    if cfg.attn_impl in ("ring", "ulysses"):
        if not sp_ok:
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r} needs a mesh with an "
                "'sp' axis (got mesh="
                f"{None if mesh is None else mesh.axis_names}); pass "
                "mesh= to forward/loss_fn or use 'flash'")
        kernel = ring_attention if cfg.attn_impl == "ring" \
            else ulysses_attention
        fn = jax.shard_map(
            partial(kernel, axis_name="sp", causal=True),
            mesh=mesh, in_specs=(_QKV, _QKV, _QKV), out_specs=_QKV,
            check_vma=False)
        return fn(q, k, v)
    if cfg.attn_impl == "dense":
        return dense_attention(q, k, v, causal=True)
    flash = partial(flash_attention, causal=True)
    if mesh is not None:
        # the Pallas kernel is a Mosaic custom call, and XLA refuses to
        # partition one: on four v5e chips the step does not compile
        # without this ("Mosaic kernels cannot be automatically
        # partitioned. Please wrap the call in a shard_map.")
        spec = _flash_spec(mesh, q.shape[0], k.shape[1])
        flash = jax.shard_map(flash, mesh=mesh,
                              in_specs=(spec, spec, spec),
                              out_specs=spec, check_vma=False)
    return flash(q, k, v)


def _flash_spec(mesh: Mesh, batch: int, kv_heads: int) -> P:
    """How flash attention's (batch, heads, seq, hd) operands are split
    over ``mesh``: batch rows over dp x fsdp and heads over tp, so each
    device runs the kernel on its own rows and heads (the sequence
    stays whole — that is what ring/ulysses are for). An axis whose
    size does not divide its dimension is left out, and that dimension
    stays whole on every device; ``kv_heads`` is the smaller head count
    under GQA, and q's is a multiple of it."""
    rows = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    if batch % math.prod(mesh.shape[a] for a in rows):
        rows = ()
    heads = "tp" if "tp" in mesh.axis_names \
        and kv_heads % mesh.shape["tp"] == 0 else None
    return P(rows or None, heads, None, None)


def _layer(cfg: LlamaConfig, mesh, cos, sin, x, lp):
    """One transformer block. x: (b, s, dim) in cfg.dtype."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, lp, h, cos, sin)
    q = constrain(q, *_QKV)
    k = constrain(k, *_QKV)
    v = constrain(v, *_QKV)
    o = _attention(cfg, q, k, v, mesh)
    x = checkpoint_name(x + constrain(_out_proj(cfg, lp, o), *_ACT),
                        "attn_resid")

    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    delta, aux = _ffn(cfg, lp, h, mesh)
    x = x + constrain(delta, *_ACT)
    return x, aux


@jax.named_scope("mlp")
def _ffn(cfg: LlamaConfig, lp, h, mesh, serving: bool = False):
    """FFN residual delta: dense SwiGLU, or the MoE expert bank when
    ``cfg.moe_experts`` is set (expert parallelism over 'ep';
    ``parallel.moe``). Returns (delta, aux) — aux is the MoE
    load-balancing term, 0 for dense. ``serving`` switches MoE to the
    EXACT dropless path (moe_ffn_dense: routing is a pure per-token
    function, linear in T) — the cached prefill/decode path uses it so
    generation never depends on batch composition."""
    dt = h.dtype
    if cfg.moe_experts:
        from ..parallel.moe import moe_ffn, moe_ffn_dense
        b, s, d = h.shape
        mp = {"gate": lp["moe_gate"], "w_gate": lp["w_gate"],
              "w_up": lp["w_up"], "w_down": lp["w_down"]}
        if serving:
            out, aux = moe_ffn_dense(mp, h.reshape(b * s, d),
                                     top_k=cfg.moe_top_k, mesh=mesh)
        else:
            out, aux = moe_ffn(mp, h.reshape(b * s, d),
                               top_k=cfg.moe_top_k,
                               capacity_factor=cfg.moe_capacity,
                               mesh=mesh)
        return out.reshape(b, s, d), aux
    # named before the activation: ``silu`` is cheap to do again
    gate = jax.nn.silu(
        checkpoint_name(h @ _wq8(lp["w_gate"], dt), "mlp_gate"))
    up = checkpoint_name(h @ _wq8(lp["w_up"], dt), "mlp_up")
    return (gate * up) @ _wq8(lp["w_down"], dt), \
        jnp.zeros((), jnp.float32)


# What a checkpointed layer can keep, in rungs: (rung, the names its
# values carry). "mlp_gate" and "mlp_up" spare the backward pass a
# 14336-wide product each (a third of a layer's operations each, 28 KiB
# a token at Mistral-7B's widths in bf16; the gate before its ``silu``,
# which is cheap to do again); "attn_out" the flash forward kernel (its
# output and its two float32 statistics a head, 8.25 KiB; only the
# Pallas path has them, so the rung is offered only there); "attn_qkv"
# the Q, K, V and ``wo`` products (q, k and v as rotated and before
# GQA's repeat, and the stream after attention: 20 KiB). The stream is
# in one rung with q because alone it spared nothing on the chip and
# cost memory (PERF.md §6, PR 37); k and v are there so that the rungs
# stay few and coarse, and a plan does not turn on a few MB: at the
# train cell's shapes the one picked holds while the free memory stays
# within -0.37 / +0.42 GB of what the chip read.
REMAT_LADDER = (
    ("mlp_gate", ("mlp_gate",)),
    ("mlp_up", ("mlp_up",)),
    ("attn_out", (ATTN_OUT_NAME, ATTN_STATS_NAME)),
    ("attn_qkv", ("attn_q", "attn_k", "attn_v", "attn_resid")),
)


def _rungs(cfg: LlamaConfig, tp: int = 1, seq_len: int = 0,
           flash_kernel: bool = True) -> dict:
    """rung -> (bytes, forward operations the backward pass is spared)
    a token and layer on one device of a mesh whose ``tp`` axis splits
    the heads and the MLP's width (the stream is whole on every one).
    An operation is priced alike in every rung: on the train cell's four
    chips two sets of equal operations, both MLP products and one of
    them with the kernel and ``attn_qkv``, spared 8.92 and 9.05 ms a
    layer (PERF.md §6, PR 37). ``seq_len`` prices the flash
    forward kernel (causal: half of ``4 s`` a head lane), which only
    ``flash_kernel`` has."""
    it = jnp.dtype(cfg.dtype).itemsize
    q = -(-cfg.n_heads // tp) * cfg.head_dim
    kv = -(-cfg.n_kv_heads // tp) * cfg.head_dim
    h = -(-cfg.hidden_dim // tp)
    d = cfg.dim
    out = {
        "mlp_gate": (h * it, 2 * d * h),
        "mlp_up": (h * it, 2 * d * h),
        "attn_out": (q * it + 2 * q // cfg.head_dim * 4, 2 * seq_len * q),
        "attn_qkv": ((q + 2 * kv + d) * it, 2 * d * (q + 2 * kv) + 2 * q * d),
    }
    if cfg.moe_experts:     # the experts' products carry no names
        del out["mlp_gate"], out["mlp_up"]
    if not flash_kernel:    # no other path names its output
        del out["attn_out"]
    return out


def remat_plan(cfg: LlamaConfig, tokens_per_device: int,
               free_bytes: Optional[int], tp: int = 1,
               seq_len: int = 0, flash_kernel: bool = True) -> tuple:
    """``(names, bytes)``: the names a checkpointed layer saves and what
    they take on a device. Of the ladder's rungs, the set that spares
    the backward pass the most operations (:func:`_rungs`) among those
    whose bytes, for ``tokens_per_device`` tokens of every layer, fit
    ``free_bytes`` (one device's; ``None`` where it is not known).
    Arithmetic on the shapes alone, at most 2**4 sets: empty at ``None``
    and at 0, never more than ``free_bytes``, never less spared for more
    free."""
    rungs = _rungs(cfg, tp, seq_len, flash_kernel)
    token_layers = tokens_per_device * cfg.n_layers
    best, spared, kept = (), 0, 0
    for n in range(1, len(rungs) + 1):
        for pick in itertools.combinations(rungs, n):
            size = token_layers * sum(rungs[r][0] for r in pick)
            ops = sum(rungs[r][1] for r in pick)
            if ops > spared and size <= (free_bytes or 0):
                best, spared, kept = pick, ops, size
    return tuple(name for rung, names in REMAT_LADDER if rung in best
                 for name in names), kept


def _axis(mesh: Optional[Mesh], *names: str) -> int:
    return math.prod(mesh.shape.get(n, 1) for n in names) if mesh else 1


def _free_bytes(cfg: LlamaConfig, params, tokens_per_device: int,
                mesh: Optional[Mesh]) -> Optional[int]:
    """What one device has left for saved activations, or ``None`` where
    that is not known: outside the trace of a train step
    (``parallel.step.traced_state_bytes``: nothing says what state the
    device will hold) and where the device reports no memory (the CPU).
    Its limit, less the state (the larger of what lies on the device
    now and what the step's state takes by its shapes: the same whether
    the state is resident or only described), less the gradients and
    the rest of what a step needs whatever a layer saves
    (:func:`_step_reserve`), less 8% of the limit kept clear."""
    state = traced_state_bytes()
    if state is None:
        return None
    dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    try:
        stats = dev.memory_stats()
    except RuntimeError:    # a described device (an AOT compile): none
        stats = None
    if not stats or "bytes_limit" not in stats:
        return None
    limit = stats["bytes_limit"]
    return int(limit - max(stats.get("bytes_in_use", 0), state)
               - limit // 100 * 8
               - _step_reserve(cfg, params, tokens_per_device, mesh))


def _step_reserve(cfg: LlamaConfig, params, tokens_per_device: int,
                  mesh: Optional[Mesh]) -> int:
    """Bytes a train step needs on a device besides its state, whatever
    its layers save, by shapes: the gradients (the parameters' bytes a
    device under :func:`sharding_rules`); every layer's input (what a
    checkpointed layer always keeps); the device's part of the layers'
    weights once more in the compute dtype (XLA casts the stacked shards
    before the loop and gathers the cast); two layers' weights gathered
    whole over fsdp in the compute dtype; two chunks of the loss's
    float32 logits. Set against the compiler's own peak for the train
    cell (PERF.md, PR 37: 2.82 GB here beside the gradients, 2.6-3.2
    there)."""
    it = jnp.dtype(cfg.dtype).itemsize
    tp, fsdp = _axis(mesh, "tp"), _axis(mesh, "fsdp")
    grads = bytes_per_device(
        params, sharding_rules(cfg).tree_specs(params), mesh)
    layer = sum(p.size for p in jax.tree.leaves(params["layers"])) \
        // cfg.n_layers // tp
    chunk = _resolve_ce_chunk(cfg) or cfg.vocab_size
    return int(grads
               + tokens_per_device * cfg.n_layers * cfg.dim * it
               + layer * cfg.n_layers // fsdp * it
               + 2 * layer * it
               + 2 * tokens_per_device * chunk * 4)


def _checkpointed(cfg: LlamaConfig, layer, params, tokens,
                  mesh: Optional[Mesh]):
    """``layer`` under ``jax.checkpoint`` as ``cfg.remat_policy`` says.
    At ``None`` the plan is made here, once a trace, from what can be
    seen: the widths, the tokens a device holds under the mesh, which
    attention runs, the device's free memory; an empty plan is plain
    ``jax.checkpoint``. A plan made for a train step goes on its
    record."""
    if cfg.remat_policy == "dots_no_batch":
        return jax.checkpoint(
            layer, policy=jax.checkpoint_policies
            .dots_with_no_batch_dims_saveable)
    if cfg.remat_policy is not None:
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r} "
            "(use None or 'dots_no_batch')")
    tp, seq = _axis(mesh, "tp"), tokens.shape[-1]
    tokens_per_device = -(-tokens.size // _axis(mesh, "dp", "fsdp", "sp"))
    kernel = cfg.attn_impl == "flash" and _flash_path(
        (1, cfg.n_heads, seq, cfg.head_dim), seq) == "pallas"
    plan, kept = remat_plan(
        cfg, tokens_per_device,
        _free_bytes(cfg, params, tokens_per_device, mesh), tp, seq, kernel)
    if traced_state_bytes() is not None:
        telemetry.record_remat_plan(plan, kept)
    if not plan:
        return jax.checkpoint(layer)
    return jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(*plan))


def forward_hidden(cfg: LlamaConfig, params, tokens,
                   mesh: Optional[Mesh] = None, with_aux: bool = False):
    """tokens: (batch, seq) int32 → final-norm hidden states
    (batch, seq, dim) in cfg.dtype — everything but the lm_head
    matmul, so losses can stream the vocab dim instead of
    materializing (B, S, V) logits. With ``with_aux`` also returns the
    per-layer-mean MoE load-balancing aux (0 for dense configs)."""
    b, s = tokens.shape
    x = constrain(_embed(cfg, params, tokens), *_ACT)
    cos, sin = rope_tables(cfg, s)

    layer = partial(_layer, cfg, mesh, cos, sin)
    if cfg.remat:
        layer = _checkpointed(cfg, layer, params, tokens, mesh)

    if cfg.scan_layers:
        def body(x, lp):
            return layer(x, lp)
        x, auxes = lax.scan(body, x, params["layers"])
        aux = jnp.mean(auxes)
    else:
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, a = layer(x, lp)
            aux = aux + a / cfg.n_layers

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x, aux) if with_aux else x


def _head(cfg: LlamaConfig, params):
    return (params["tok_embed"].T if cfg.tie_embeddings
            else params["lm_head"])


def _wq8(w, dt):
    """Serving weight loader: a raw array, or a weight-only int8 dict
    ``{'q8': int8, 's8': f32 per-out-channel}`` (see
    :func:`quantize_params_int8`). The dequant multiply is in-program;
    XLA fuses it into the consuming matmul's operand read, so int8
    halves the HBM weight traffic that dominates small-batch decode."""
    if isinstance(w, dict):
        return w["q8"].astype(dt) * w["s8"].astype(dt)
    return w.astype(dt)


def quantize_params_int8(cfg: LlamaConfig, params):
    """Weight-only int8 quantization for SERVING (prefill/decode/
    generate — the cached path; the training forward does not consume
    quantized trees). Symmetric per-output-channel scales over the
    contracted axis: ``w ≈ q8 · s8`` with q8 ∈ [-127, 127] int8 and
    s8 = max|w| / 127 per output column. Activations, norms, and the
    KV cache stay in ``cfg.dtype`` — this is the regime analysis of
    docs/perf.md ("int8 serving becomes interesting only where
    weights dominate the step time — multi-GB models at small
    batch"): llama3_8b tp8 decode. Shard with
    :func:`int8_sharding_rules`."""
    if cfg.moe_experts:
        raise NotImplementedError(
            "int8 serving quantization covers dense configs; the MoE "
            "expert banks serve via the dense-mixture path in bf16")

    def q(w):
        s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                    keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-12)
        q8 = jnp.clip(jnp.round(w.astype(jnp.float32) / s),
                      -127, 127).astype(jnp.int8)
        return {"q8": q8, "s8": s.astype(jnp.float32)}

    out = {"tok_embed": q(params["tok_embed"]),
           "final_norm": params["final_norm"],
           "layers": dict(params["layers"])}
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        out["layers"][name] = q(params["layers"][name])
    if "lm_head" in params:
        out["lm_head"] = q(params["lm_head"])
    return out


def int8_sharding_rules(cfg: Optional[LlamaConfig] = None) \
        -> ShardingRules:
    """Placement for :func:`quantize_params_int8` trees: q8 leaves
    inherit their weight's Megatron spec; s8 scales (size-1 on every
    axis but the output channels) shard only the output axis."""
    L = None
    return ShardingRules([
        (r"tok_embed/q8$",        P("tp", "fsdp")),
        (r"tok_embed/s8$",        P(None, "fsdp")),
        (r"layers/w[qkv]/q8$",    P(L, "fsdp", "tp")),
        (r"layers/w[qkv]/s8$",    P(L, None, "tp")),
        (r"layers/wo/q8$",        P(L, "tp", "fsdp")),
        (r"layers/wo/s8$",        P(L, None, "fsdp")),
        (r"layers/w_(gate|up)/q8$", P(L, "fsdp", "tp")),
        (r"layers/w_(gate|up)/s8$", P(L, None, "tp")),
        (r"layers/w_down/q8$",    P(L, "tp", "fsdp")),
        (r"layers/w_down/s8$",    P(L, None, "fsdp")),
        (r"lm_head/q8$",          P("fsdp", "tp")),
        (r"lm_head/s8$",          P(None, "tp")),
        (r"norm",                 P()),
        (r".*",                   P()),
    ])


def forward(cfg: LlamaConfig, params, tokens,
            mesh: Optional[Mesh] = None):
    """tokens: (batch, seq) int32 → logits (batch, seq, vocab) f32."""
    x = forward_hidden(cfg, params, tokens, mesh=mesh)
    return constrain(_lm_head(cfg, params, x), ("dp", "fsdp"), "sp", None)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------
def chunked_softmax_xent(x, head, targets, chunk: int):
    """Per-token causal-LM NLL via a streaming logsumexp over vocab
    chunks — the full (B, S, V) logits tensor is NEVER materialized
    (VERDICT r2 #5: at seq 2048 × vocab 32k the f32 logits alone are
    ~1 GB and dominate the llama step's HBM traffic).

    x: (b, s, d) compute dtype; head: (d, V); targets: (b, s) int.
    Each scan step matmuls one (d, chunk) slice (MXU-friendly N =
    chunk), folds it into running (max, sumexp, target-logit) carries
    of shape (b, s), and is wrapped in ``jax.checkpoint`` so the
    backward recomputes chunk logits instead of saving them.
    """
    b, s, d = x.shape
    V = head.shape[1]
    n_chunks = -(-V // chunk)
    Vp = n_chunks * chunk
    if Vp != V:           # zero-pad; padded cols masked to -inf below
        head = jnp.pad(head, ((0, 0), (0, Vp - V)))

    def body(carry, i):
        m, acc, tl = carry
        W = lax.dynamic_slice_in_dim(head, i * chunk, chunk, axis=1)
        logits = jnp.einsum("bsd,dv->bsv", x, W,
                            preferred_element_type=jnp.float32)
        col0 = i * chunk
        if Vp != V:
            cols = col0 + jnp.arange(chunk)
            logits = jnp.where(cols < V, logits, -jnp.inf)
        cm = logits.max(-1)
        nm = jnp.maximum(m, cm)
        acc = acc * jnp.exp(m - nm) + \
            jnp.exp(logits - nm[..., None]).sum(-1)
        local = targets - col0
        hit = (local >= 0) & (local < chunk)
        got = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[..., None],
            axis=-1)[..., 0]
        tl = tl + jnp.where(hit, got, 0.0)
        return (nm, acc, tl), None

    init = (jnp.full((b, s), -jnp.inf, jnp.float32),
            jnp.zeros((b, s), jnp.float32),
            jnp.zeros((b, s), jnp.float32))
    (m, acc, tl), _ = lax.scan(jax.checkpoint(body), init,
                               jnp.arange(n_chunks))
    return m + jnp.log(acc) - tl


def _resolve_ce_chunk(cfg: LlamaConfig) -> int:
    """0 = no chunking. Auto mode picks ~8k-wide chunks (a good MXU N)
    once the vocab is big enough for logits to dominate HBM."""
    if cfg.ce_chunk is None or cfg.ce_chunk is False:
        return 0                       # explicit opt-out
    if cfg.ce_chunk == 0:              # auto
        return 8192 if cfg.vocab_size > 16384 else 0
    return int(cfg.ce_chunk)


def loss_fn(cfg: LlamaConfig, mesh: Optional[Mesh] = None):
    """Causal-LM loss for ``parallel.step.make_train_step``: batch is a
    dict with 'tokens' (b, s) and optional 'mask' (b, s) — predicts
    token t+1 from prefix ≤ t. Large vocabs take the chunked-CE path
    (see ``chunked_softmax_xent``). Pass the step's ``mesh``: ring and
    ulysses attention need it, and so does flash attention on more
    than one TPU chip (its kernel runs per device under shard_map)."""
    def loss(params, batch):
        tokens = batch["tokens"]
        x, moe_aux = forward_hidden(cfg, params, tokens, mesh=mesh,
                                    with_aux=True)
        x = x[:, :-1]
        targets = tokens[:, 1:]
        mask = batch.get("mask")
        mask = (jnp.ones_like(targets, jnp.float32) if mask is None
                else mask[:, 1:].astype(jnp.float32))
        chunk = _resolve_ce_chunk(cfg)
        # the head matmul, the softmax and the NLL, chunked or not
        with jax.named_scope("xent"):
            head = _head(cfg, params).astype(cfg.dtype)
            if chunk:
                nll = chunked_softmax_xent(x, head, targets, chunk)
            else:
                logits = jnp.einsum("bsd,dv->bsv", x, head,
                                    preferred_element_type=jnp.float32)
                logits = constrain(logits, ("dp", "fsdp"), "sp", None)
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(logp, targets[..., None],
                                           axis=-1)[..., 0]
            ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        if cfg.moe_experts:
            ce = ce + cfg.moe_aux_weight * moe_aux
        return ce
    return loss


# ---------------------------------------------------------------------------
# inference: KV-cache prefill + decode (VERDICT r2 #4)
# ---------------------------------------------------------------------------
# The reference shipped a dedicated fixed-graph inference surface
# (``src/c_api/c_predict_api.cc`` + ``benchmark_score.py`` [path cites
# — unverified]); the TPU-era equivalent for a causal LM is
# prefill-then-decode over a preallocated KV cache: static shapes
# throughout (cache sized to max_len, position as a traced scalar), so
# the whole generate loop compiles to ONE program with a lax.scan —
# no per-token dispatch, no dynamic shapes.
#
# Sharded serving (VERDICT r3 #1): at 8B scale a single chip cannot
# hold the weights (16GB bf16 vs 16GB v5e HBM, before the cache), so
# decode is mesh-first: pass ``mesh=`` and the cache shards over the
# kv-head axis (tp) and the batch axis (dp/fsdp) while the params keep
# their rule-table placement — the same Megatron layout the train step
# uses, so a trained sharded state serves without resharding.

def cache_specs(cfg: LlamaConfig, mesh: Mesh, batch_size: int):
    """PartitionSpecs for the KV cache on ``mesh``: batch over the
    data axes, kv heads over tp. An axis is dropped when the mesh
    lacks it or the dim isn't divisible (tiny test configs / odd
    batches) — a dropped axis means replication, never an error."""
    batch_axes = tuple(a for a in ("dp", "fsdp")
                       if a in mesh.axis_names and mesh.shape[a] > 1)
    nb = 1
    for a in batch_axes:
        nb *= mesh.shape[a]
    if batch_axes and batch_size % nb:
        batch_axes = ()
    tp = ("tp" if "tp" in mesh.axis_names
          and cfg.n_kv_heads % mesh.shape["tp"] == 0 else None)
    kv = P(None, batch_axes if batch_axes else None, tp, None, None)
    return {"k": kv, "v": kv, "pos": P()}


def init_cache(cfg: LlamaConfig, batch_size: int, max_len: int,
               mesh: Optional[Mesh] = None):
    """Preallocated GQA KV cache: (L, b, n_kv_heads, max_len, hd) in
    the compute dtype, plus the traced write position. With ``mesh``
    the cache materializes directly sharded per :func:`cache_specs` —
    it never stages through one device (an 8B 8k-context cache is
    larger than a v5e chip's HBM)."""
    hd = cfg.head_dim
    shape = (cfg.n_layers, batch_size, cfg.n_kv_heads, max_len, hd)

    def build():
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype),
                "pos": jnp.zeros((), jnp.int32)}

    if mesh is None:
        return build()
    from jax.sharding import NamedSharding
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        cache_specs(cfg, mesh, batch_size),
        is_leaf=lambda s: isinstance(s, P))
    return jax.jit(build, out_shardings=shardings)()


# (the decode path's explicit-mesh constraints use sharding.mcon,
# imported as _mcon above)


def _layer_cached(cfg: LlamaConfig, cos, sin, pos, max_len,
                  mesh, kvspec, x, lp, ck, cv):
    """One block over the cache. x: (b, s, dim) where s is the prompt
    length (prefill) or 1 (decode). ck/cv: (b, kvh, max_len, hd).
    Returns (x, ck, cv) with the new keys/values written at
    [pos : pos+s]. ``kvspec`` is the per-layer cache PartitionSpec
    (cache_specs minus the scanned layer dim); with a mesh the cache
    write is pinned to it so XLA never re-lays the cache mid-scan."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    dt = cfg.dtype

    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, lp, h, cos, sin)
    # pin the batch + head axes — the reshape/transpose chain above can
    # lose the propagated sharding, and a lost head sharding makes the
    # attention materialize the full cache per device. BOTH axes come
    # from the cache spec (kvspec[0]/[1]) so the pins honor the same
    # divisibility guards cache_specs applies: an odd batch or a tp
    # that doesn't divide the kv heads replicates that axis everywhere
    # instead of fighting the cache with a per-layer reshard.
    batch_ax = kvspec[0] if kvspec is not None else ("dp", "fsdp")
    head_ax = kvspec[1] if kvspec is not None else None
    q = _mcon(mesh, q, batch_ax, head_ax, None, None)
    k = _mcon(mesh, k, batch_ax, head_ax, None, None)
    v = _mcon(mesh, v, batch_ax, head_ax, None, None)
    with jax.named_scope(KV_WRITE_SCOPE):
        zero = jnp.zeros((), jnp.int32)
        idx = (zero, zero, pos.astype(jnp.int32), zero)
        ck = lax.dynamic_update_slice(ck, k.astype(dt), idx)
        cv = lax.dynamic_update_slice(cv, v.astype(dt), idx)
    if mesh is not None:
        from jax.sharding import NamedSharding
        ck = lax.with_sharding_constraint(
            ck, NamedSharding(mesh, kvspec))
        cv = lax.with_sharding_constraint(
            cv, NamedSharding(mesh, kvspec))

    # attend q against the whole cache, masked to the causal prefix:
    # key j visible to query i iff j <= pos + i. GQA-native: group the
    # q heads per kv head instead of materializing repeated KV (the
    # repeat would copy the whole cache every layer, every step)
    with jax.named_scope(ATTENTION_SCOPE):
        rep = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(b, cfg.n_kv_heads, rep, s, hd)
        logits = jnp.einsum("bgrsd,bgkd->bgrsk", qg, ck,
                            preferred_element_type=jnp.float32)
        logits = logits / math.sqrt(hd)
        kpos = jnp.arange(max_len)[None, :]             # (1, max_len)
        qpos = pos + jnp.arange(s)[:, None]             # (s, 1)
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1).astype(dt)
        o = jnp.einsum("bgrsk,bgkd->bgrsd", p, cv)
        o = o.reshape(b, cfg.n_heads, s, hd)
    x = x + _mcon(mesh, _out_proj(cfg, lp, o), batch_ax, None, None)

    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    # serving: exact dropless routing — generation must not depend on
    # how many tokens share this step (decode sees T=batch, prefill
    # T=batch·s), and capacity tensors must stay linear in T
    delta, _ = _ffn(cfg, lp, h, mesh, serving=True)
    x = x + _mcon(mesh, delta, batch_ax, None, None)
    return x, ck, cv


def _forward_cached(cfg: LlamaConfig, params, tokens, cache,
                    last_only: bool = False,
                    mesh: Optional[Mesh] = None,
                    last_index=None):
    """Shared prefill/decode body: runs the stack over the cache and
    returns (logits (b, s, V) f32, new cache). ``last_only`` applies
    the lm_head to the final position only — generation never needs
    (and must not pay for) full-prompt logits. ``last_index`` (a traced
    scalar) instead applies it to that single position — the bucketed
    serving prefill pads prompts to a bucket, so "last" is the last
    REAL position, not the last row. ``mesh`` pins the cache
    and residual-stream shardings (see ``cache_specs``); params attend
    against the cache in their training placement, so the tp einsums
    stay local and XLA reduces over tp exactly where the Megatron
    layout implies."""
    b, s = tokens.shape
    max_len = cache["k"].shape[3]
    pos = cache["pos"]
    kvspec = (cache_specs(cfg, mesh, b)["k"] if mesh is not None
              else None)
    if kvspec is not None:               # per-layer view: drop the
        kvspec = P(*kvspec[1:])          # scanned leading L axis
    batch_ax = kvspec[0] if kvspec is not None else ("dp", "fsdp")
    x = _mcon(mesh, _embed(cfg, params, tokens), batch_ax, None, None)
    # rope tables for absolute positions pos..pos+s from one static
    # (max_len, hd/2) table — keeps the program shape-static
    cos_t, sin_t = rope_tables(cfg, max_len)
    cos = lax.dynamic_slice_in_dim(cos_t, pos, s, axis=0)
    sin = lax.dynamic_slice_in_dim(sin_t, pos, s, axis=0)

    def body(x, xs):
        lp, ck, cv = xs
        x, ck, cv = _layer_cached(cfg, cos, sin, pos, max_len,
                                  mesh, kvspec, x, lp, ck, cv)
        return x, (ck, cv)

    x, (ck, cv) = lax.scan(body, x,
                           (params["layers"], cache["k"], cache["v"]))
    if mesh is not None:
        # the scan re-stacks the per-layer cache; pin the stacked
        # result or the whole cache round-trips through a replicated
        # temp (full-cache bytes per device)
        from jax.sharding import NamedSharding
        full = NamedSharding(mesh, cache_specs(cfg, mesh, b)["k"])
        ck = lax.with_sharding_constraint(ck, full)
        cv = lax.with_sharding_constraint(cv, full)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_index is not None:
        x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    elif last_only:
        x = x[:, -1:]
    logits = _mcon(mesh, _lm_head(cfg, params, x), batch_ax, None, None)
    new_cache = {"k": ck, "v": cv, "pos": pos + s}
    return logits, new_cache


def prefill(cfg: LlamaConfig, params, tokens, cache,
            mesh: Optional[Mesh] = None, last_only: bool = False):
    """Run the prompt through the stack, filling the cache. Returns
    (logits (b, s, V) f32 for every prompt position, cache). Serving
    only consumes the final position — pass ``last_only=True`` and s=1
    comes back; at 8B the full-prompt logits are the prefill peak
    (8×2048×128256 f32 ≈ 8.4GB, vs ~0.004GB for the last position)."""
    return _forward_cached(cfg, params, tokens, cache, mesh=mesh,
                           last_only=last_only)


def chunked_prefill(cfg: LlamaConfig, params, tokens, cache,
                    chunk_size: int, mesh: Optional[Mesh] = None):
    """Streaming prefill (VERDICT r4 #5 — the long-context serving
    half): run the prompt through the cached stack in ``chunk_size``
    slices via one ``lax.scan``, so peak activation memory scales
    with the CHUNK, not the prompt. Single-shot prefill materializes
    per-layer attention logits of (b, h, s, ctx) f32 — at llama3_8b
    with a 32k prompt that is ~1 TB and cannot compile; chunked at
    1k it is ~34 GB/layer-step sharded over tp. Only the final
    position's logits are computed per chunk (s=1 head matmul), and
    only the last chunk's survive.

    Prompt lengths that don't divide ``chunk_size`` are handled by a
    trailing remainder pass (a second compiled shape) — NEVER pad the
    prompt: the cached path has no pad masking, so pad tokens would
    occupy real cache slots and shift every RoPE position.

    Returns (logits (b, 1, V) f32 for the last prompt position,
    cache) — exactly ``prefill(..., last_only=True)``
    (``test_llama_chunked_prefill_matches_single_shot``)."""
    b, s = tokens.shape
    n, rem = divmod(s, chunk_size)
    logits = None
    if n == 1 and rem == 0:
        return _forward_cached(cfg, params, tokens, cache,
                               last_only=True, mesh=mesh)
    if n:
        # (b, n·c) → (n, b, c): scan consumes the leading axis. The
        # per-chunk logits ride in the CARRY (same (b, 1, V) shape
        # every step), not the stacked scan output — stacking n
        # last-position logits would buffer n·b·V f32 (~123 MB at
        # 32k/llama3_8b) only to keep one slice
        chunks = tokens[:, :n * chunk_size] \
            .reshape(b, n, chunk_size).transpose(1, 0, 2)

        def body(carry, chunk):
            cache, _ = carry
            lg, cache = _forward_cached(cfg, params, chunk, cache,
                                        last_only=True, mesh=mesh)
            return (cache, lg), None

        zeros = jnp.zeros((b, 1, cfg.vocab_size), jnp.float32)
        (cache, logits), _ = lax.scan(body, (cache, zeros), chunks)
    if rem:
        logits, cache = _forward_cached(cfg, params,
                                        tokens[:, n * chunk_size:],
                                        cache, last_only=True,
                                        mesh=mesh)
    return logits, cache


def decode_step(cfg: LlamaConfig, params, token, cache,
                mesh: Optional[Mesh] = None):
    """One autoregressive step. token: (b, 1) int32. Returns
    (logits (b, V) f32 for the next position, cache)."""
    logits, cache = _forward_cached(cfg, params, token, cache,
                                    mesh=mesh)
    return logits[:, 0], cache


@jax.named_scope(SAMPLER_SCOPE)
def sample_logits(rng, lg, temperature=0.0, top_k=None, top_p=None,
                  mesh: Optional[Mesh] = None):
    """THE sampler — one shared helper for :func:`generate` and the
    continuous-batching serving engine (``mxtpu.serve``). lg: (b, V)
    f32 logits → (b,) int32 tokens.

    Two calling modes, numerically aligned token-for-token:

    - **static** (all of temperature/top_k/top_p are Python numbers or
      None): specializes the jitted graph per config — greedy compiles
      to a bare argmax, top-k uses ``lax.top_k`` — the fast path
      ``generate``'s one-program decode loop wants.
    - **traced** (any of them a jax/numpy array): one graph serves
      every per-row mix — temperature (b,), top_k (b,) ints (vocab
      size disables), top_p (b,) (1.0 disables), with temperature 0
      rows selecting argmax. This is how the serving engine runs
      requests with different sampling configs through ONE compiled
      decode program, with tokens bit-matching the static path: the
      top-k threshold is the same kth VALUE, the nucleus cut-off the
      same search, so the masked logits agree and
      ``jax.random.categorical`` sees identical inputs.

    Nucleus semantics (both modes): keep the smallest prefix of the
    sorted distribution whose mass reaches p (the top token always
    survives, a tie-class is kept or cut as a whole), applied as a
    value threshold rather than a full-vocab scatter.

    No order is built. The sampler needs two numbers a row, the k-th
    largest value and the nucleus cut-off, and both are monotone in the
    threshold: the COUNT of values at or above ``v`` and the MASS of
    values above ``v`` only fall as ``v`` rises. ``ops.threshold``
    finds each by bisection on the float32's ordered bit pattern, 32
    probes of a compare, a select and a sum over the row (on a TPU one
    Pallas kernel with the rows resident in VMEM, for a block of eight
    rows or more; ``jnp`` elsewhere and for fewer),
    exact for any distribution, and skips a search that no row asks for
    (``top_k`` off, ``top_p`` 1, a greedy row). A sort of a 200064-wide
    row was the costliest operation of a decode step (PR 33), and a
    permutation with a ``take_along_axis`` through it a
    vocabulary-sized gather a row besides. ``mesh``: the mesh the
    logits are sharded over, if any (the kernel is not partitioned)."""
    static = (isinstance(temperature, (int, float))
              and (top_k is None or isinstance(top_k, int))
              and (top_p is None or isinstance(top_p, (int, float))))
    V = lg.shape[-1]
    if static:
        if temperature == 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        lg = lg / temperature
        if top_k is not None and top_k < V:
            kth = lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        if top_p is not None and top_p < 1.0:
            lg = _nucleus_mask(lg, top_p, mesh)
        return jax.random.categorical(rng, lg, axis=-1) \
            .astype(jnp.int32)
    greedy, slg = _masked_logits(lg, temperature, top_k, top_p, mesh)
    sampled = jax.random.categorical(rng, slg, axis=-1) \
        .astype(jnp.int32)
    return jnp.where(greedy >= 0, greedy, sampled)


def _masked_logits(lg, temperature, top_k, top_p, mesh=None):
    """The traced mode's rows as ``categorical`` will see them. lg: (b,
    V); temperature, top_k, top_p: None, scalars or (b,). Returns
    (greedy (b,) int32: the argmax of a temperature-0 row, -1 for a row
    that samples; the logits over the temperature, masked to the top-k
    values and, of those, the top-p nucleus)."""
    V = lg.shape[-1]

    def col(x, dtype):          # broadcast a scalar or (b,) over vocab
        x = jnp.asarray(x, dtype)
        x = x.reshape(x.shape + (1,) * (lg.ndim - x.ndim))
        return jnp.broadcast_to(x, lg.shape[:-1] + (1,))

    t_col = col(temperature, jnp.float32)
    samples = t_col != 0.0
    # a greedy row asks for no threshold: its argmax is its token
    k_col = jnp.where(samples, jnp.clip(
        col(V if top_k is None else top_k, jnp.int32), 1, V), V)
    p_col = jnp.where(samples,
                      col(1.0 if top_p is None else top_p, jnp.float32),
                      1.0)
    greedy = jnp.where(jnp.squeeze(samples, -1), -1,
                       jnp.argmax(lg, axis=-1).astype(jnp.int32))
    slg = lg / jnp.where(samples, t_col, 1.0)
    # top-k as a value threshold: the kth-largest VALUE equals
    # lax.top_k's kth element, so the mask matches the static path;
    # the nucleus cut-off is found over the top-k survivors
    kth, cutoff = thresholds(slg, k_col, p_col, mesh=mesh)
    return greedy, jnp.where(slg >= jnp.maximum(kth, cutoff), slg,
                             -jnp.inf)


def _nucleus_mask(lg, top_p, mesh=None):
    """Mask lg to the top-p nucleus, the survivor set applied as a >=
    threshold on the kept minimum — no full-vocab scatter."""
    rows = lg.shape[:-1] + (1,)
    _, cutoff = thresholds(lg, jnp.full(rows, lg.shape[-1], jnp.int32),
                           jnp.full(rows, top_p, jnp.float32), mesh=mesh)
    return jnp.where(lg >= cutoff, lg, -jnp.inf)


def _draw(key, greedy, row):
    """One token of one chain: ``greedy`` where the row is greedy, else
    ``categorical`` on the masked (V,) row, as :func:`sample_logits`
    draws it from a (1, V) batch."""
    tok = jax.random.categorical(key, row[None], axis=-1)[0] \
        .astype(jnp.int32)
    return jnp.where(greedy >= 0, greedy, tok)


@jax.named_scope(SAMPLER_SCOPE)
def _sample_slots(rngs, lg, temperature, top_k, top_p,
                  mesh: Optional[Mesh] = None):
    """A decode step's sample for a bank of slots, mirroring generate's
    step slot by slot: the two thresholds of every row found at once
    over the (S, V) block, then each slot splits its own chain and
    draws from its own masked row, so equal masks give equal tokens.
    Returns (carry keys (S, 2), tokens (S,))."""
    greedy, masked = _masked_logits(lg, temperature, top_k, top_p, mesh)
    keys = jax.vmap(jax.random.split)(rngs)
    return keys[:, 0], jax.vmap(_draw)(keys[:, 1], greedy, masked)


def generate(cfg: LlamaConfig, params, prompt, max_new_tokens: int,
             *, temperature: float = 0.0,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             mesh: Optional[Mesh] = None):
    """Autoregressive generation: prefill + a lax.scan of decode
    steps — ONE jitted program end to end when wrapped in jax.jit
    (max_new_tokens static). temperature=0 is greedy; otherwise
    softmax sampling at the given temperature, optionally truncated to
    the ``top_k`` highest-probability tokens and/or the ``top_p``
    nucleus (smallest prefix of the sorted distribution reaching p —
    both static-shaped: masks, not dynamic vocab slices). With
    ``mesh`` the whole loop runs sharded (cache per
    :func:`cache_specs`, params as placed) — serving the 8B flagship
    needs this: its weights alone exceed one v5e chip's HBM.

    Returns (b, prompt_len + max_new_tokens) tokens."""
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    b, s0 = prompt.shape
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    # init_cache(mesh=) materializes the cache directly sharded: under
    # an outer jit the nested jit's out_shardings become constraints,
    # and called EAGERLY (GluonLlama.generate) the full cache never
    # stages through one device — at 8B that transient replicated
    # cache would be 8.6GB on the default chip
    cache = init_cache(cfg, b, s0 + max_new_tokens, mesh=mesh)
    logits, cache = _forward_cached(cfg, params, prompt, cache,
                                    last_only=True, mesh=mesh)

    def sample(rng, lg):
        return sample_logits(rng, lg, temperature=temperature,
                             top_k=top_k, top_p=top_p, mesh=mesh)

    rng, sub = jax.random.split(rng)
    first = sample(sub, logits[:, -1])

    def step(carry, _):
        cache, tok, rng = carry
        logits, cache = decode_step(cfg, params, tok[:, None], cache,
                                    mesh=mesh)
        rng, sub = jax.random.split(rng)
        nxt = sample(sub, logits)
        return (cache, nxt, rng), nxt

    (cache, _, _), rest = lax.scan(
        step, (cache, first, rng), None, length=max_new_tokens - 1)
    out = jnp.concatenate(
        [prompt, first[:, None], rest.transpose(1, 0)], axis=1)
    return out


# ---------------------------------------------------------------------------
# continuous-batching serving (the model half of ``mxtpu.serve`` — the
# scheduler, the queue and the page allocator live there)
# ---------------------------------------------------------------------------
# ``generate`` above is a WHOLE-BATCH program: every request starts
# together and holds its cache until the slowest one finishes. The
# serving programs instead run ``max_slots`` independent sequences:
# admission seats a request in a free slot (Orca-style iteration-level
# scheduling), per-slot length/position vectors drive ONE compiled
# decode program over every slot, and the length-masked decode
# attention confines each slot to its own prefix. Prompts prefill
# through per-bucket programs (padded to a power of two), so total
# compilations stay bounded by the bucket count + 2. Two kinds of
# program follow: the detached prefill a disaggregated prefill worker
# runs (no serving state at all), and the paged programs the engine
# runs over its KV page pool.

# ---------------------------------------------------------------------------
# disaggregated prefill/decode (DistServe, OSDI '24): prefill is
# compute-bound, decode is memory-bound — the serving gateway runs them
# on separate worker pools with a KV handoff in between. Its device
# halves: ``prefill_detached`` is ``prefill_slot_paged`` minus the
# pool (it RETURNS the per-request KV block instead of scattering it),
# and ``inject_paged_kv`` (below, with the paged programs) is the
# scatter alone, run later on the decode worker's pool. Same forward
# graph, same sampler, same rng chain — so a prefill→handoff→decode
# request is bit-identical to the colocated path (tier-1-gated in
# tests/test_gateway.py).
# ---------------------------------------------------------------------------

def prefill_detached(cfg: LlamaConfig, params, tokens, true_len, rng,
                     temperature, top_k, top_p,
                     mesh: Optional[Mesh] = None):
    """Prefill ONE request without any serving state: run the
    END-padded prompt (see :func:`prefill_slot_paged` for why end
    padding is exact) through the cached stack and return the pieces a
    decode worker needs — ``(first_token (1,), k_block, v_block,
    new_rng)`` with
    k/v blocks shaped (L, n_kv_heads, bucket, hd). One compiled
    program per prompt bucket, exactly like ``prefill_slot_paged``."""
    b, bucket = tokens.shape
    hd = cfg.head_dim
    tmp = {"k": jnp.zeros((cfg.n_layers, b, cfg.n_kv_heads, bucket,
                           hd), cfg.dtype),
           "v": jnp.zeros((cfg.n_layers, b, cfg.n_kv_heads, bucket,
                           hd), cfg.dtype),
           "pos": jnp.zeros((), jnp.int32)}
    true_len = jnp.asarray(true_len, jnp.int32)
    logits, tmp = _forward_cached(cfg, params, tokens, tmp, mesh=mesh,
                                  last_index=true_len - 1)
    rng, sub = jax.random.split(rng)
    tok = sample_logits(sub, logits[:, 0], temperature=temperature,
                        top_k=top_k, top_p=top_p, mesh=mesh)
    k_block, v_block = tmp["k"][:, 0], tmp["v"][:, 0]
    if mesh is not None:
        # the block leaves the device for the wire — replicate it so
        # the host gather is one copy, not a reshard
        tok = _mcon(mesh, tok, None)
        k_block = _mcon(mesh, k_block, None, None, None, None)
        v_block = _mcon(mesh, v_block, None, None, None, None)
    return tok, k_block, v_block, rng


def prefill_detached_chunk(cfg: LlamaConfig, params, chunk, cache,
                           true_len, rng, temperature, top_k, top_p,
                           mesh: Optional[Mesh] = None):
    """One chunk of a STREAMED detached prefill: run ``chunk`` (1, cw)
    — positions ``cache["pos"]`` .. ``pos+cw`` of the END-padded
    prompt — through the cached stack and return this chunk's
    just-computed K/V rows so the worker can ship their page frames
    over the wire WHILE the next chunk computes. Iterating this over
    the whole bucket is the same math as one :func:`prefill_detached`
    call: each position's attention masks the same causal prefix of
    the same bucket-sized cache, and the sampler splits the SAME
    request key once — so the streamed handoff stays bit-identical to
    the one-shot path (the disagg bit-identity gate covers it). One
    compiled program per (chunk width, bucket) pair.

    ``cache``: the (L, 1, n_kv_heads, bucket, hd) running buffers +
    ``pos``, carried across chunk calls (zeros at pos 0). Returns
    ``(tok (1,), k_chunk, v_chunk, new_rng, new_cache)`` with
    k/v_chunk shaped (L, n_kv_heads, cw, hd). ``tok``/``new_rng`` are
    meaningful only from the chunk containing position
    ``true_len - 1`` — the worker keeps that chunk's and discards the
    rest (later chunks sample from padding logits; harmless garbage,
    never emitted)."""
    b, cw = chunk.shape
    true_len = jnp.asarray(true_len, jnp.int32)
    # the last REAL position, local to this chunk (clamped: chunks
    # before/after the one holding true_len-1 sample garbage)
    li = jnp.clip(true_len - 1 - cache["pos"], 0, cw - 1)
    logits, cache = _forward_cached(cfg, params, chunk, cache,
                                    mesh=mesh, last_index=li)
    rng, sub = jax.random.split(rng)
    tok = sample_logits(sub, logits[:, 0], temperature=temperature,
                        top_k=top_k, top_p=top_p, mesh=mesh)
    pos0 = cache["pos"] - cw
    k_chunk = lax.dynamic_slice_in_dim(cache["k"][:, 0], pos0, cw,
                                       axis=2)
    v_chunk = lax.dynamic_slice_in_dim(cache["v"][:, 0], pos0, cw,
                                       axis=2)
    if mesh is not None:
        tok = _mcon(mesh, tok, None)
        k_chunk = _mcon(mesh, k_chunk, None, None, None, None)
        v_chunk = _mcon(mesh, v_chunk, None, None, None, None)
    return tok, k_chunk, v_chunk, rng, cache


# ---------------------------------------------------------------------------
# paged serving: fixed-size KV page pool + per-slot page tables
# (PagedAttention, Kwon et al. SOSP '23). A cache row per slot would
# reserve max_len KV whether or not a request ever grows there; the
# engine keeps ONE pool of (L, n_pages, page_size, kvh, hd) and maps
# each slot's logical sequence through an int32 page-table row the
# host owns. Admission is bounded by free PAGES, not
# slots, and read-only pages can be shared between slots (refcounted
# copy-on-write prefix sharing — the allocator lives in
# ``mxtpu.serve.engine``; these are its device halves). Page 0 is
# scratch: the engine never hands it out, zeroed table rows alias it,
# and redirected writes land there harmlessly.
#
# The pool is stored TOKEN-MAJOR: (layer, page, in-page offset) lead,
# because those are the dimensions the decode write indexes
# (``.at[l, phys, off]``). XLA lays a scatter's operand out with its
# indexed dimensions leading; stored any other way, the donated pool
# is copied whole into that layout and back every step. The decode
# programs carry the whole pool through the layer loop and reach it by
# index, so the write and the page gather are the only operations that
# touch it. Every page index a program sees lies in [0, n_pages):
# table rows hold pages the allocator handed out or scratch page 0,
# which is what lets the gathers say ``promise_in_bounds`` and skip
# the out-of-bounds fill over every gathered row.
# ---------------------------------------------------------------------------

def paged_cache_specs(cfg: LlamaConfig, mesh: Mesh):
    """PartitionSpecs for the paged pool: kv heads over tp (axis 3 of
    the token-major (L, n_pages, page_size, kvh, hd) pool; dropped
    when tp doesn't divide them — replication, never an error), layer,
    page and in-page offset unsharded (they are what the decode write indexes,
    and the host scatters single pages). Scale pools (int8 mode,
    (L, n_pages, page_size, kvh)) follow the same spec. Per-slot
    vectors are replicated."""
    tp = ("tp" if "tp" in mesh.axis_names
          and cfg.n_kv_heads % mesh.shape["tp"] == 0 else None)
    # trailing Nones trimmed: program outputs come back normalized, and
    # a committed P(..., 'tp', None) vs an output P(..., 'tp') would be
    # unequal jit cache keys — one spurious recompile per program on
    # the mesh path
    kv = P(None, None, None, tp) if tp is not None else P()
    return {"k": kv, "v": kv, "ks": kv, "vs": kv,
            "lengths": P(), "tokens": P(), "rngs": P()}


def init_paged_cache(cfg: LlamaConfig, max_slots: int, n_pages: int,
                     page_size: int, mesh: Optional[Mesh] = None,
                     int8: bool = False):
    """The serving engine's device state: K/V pools of
    (L, n_pages, page_size, n_kv_heads, hd) in the compute dtype plus
    per-slot ``lengths`` (valid cache entries), ``tokens`` (next input
    token) and ``rngs`` (per-request sampling chains); with ``mesh``
    it materializes directly sharded per :func:`paged_cache_specs`
    (page tables stay HOST-side — a small int32 operand per step, so
    table edits never touch device state). ``int8=True`` stores the
    pools as int8 with per-token-per-head f32 scales ``ks``/``vs`` of
    (L, n_pages, page_size, kvh) — KV HBM halves again; dequant happens
    on gather (deterministic, not bit-exact with the f32 pool —
    docs/serving.md).

    The layout is token-major so that (layer, page, offset) — the
    dimensions the decode write ``.at[l, phys, off]`` indexes — lead:
    the default layout is then the one XLA wants for that scatter, and
    the donated pool is updated in place instead of being copied into
    the scatter's layout and back every step (the section comment
    above has the whole argument)."""
    hd = cfg.head_dim
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, hd)

    def build():
        if int8:
            pools = {"k": jnp.zeros(shape, jnp.int8),
                     "v": jnp.zeros(shape, jnp.int8),
                     "ks": jnp.ones(shape[:4], jnp.float32),
                     "vs": jnp.ones(shape[:4], jnp.float32)}
        else:
            pools = {"k": jnp.zeros(shape, cfg.dtype),
                     "v": jnp.zeros(shape, cfg.dtype)}
        pools.update({
            "lengths": jnp.zeros((max_slots,), jnp.int32),
            "tokens": jnp.zeros((max_slots,), jnp.int32),
            "rngs": jnp.zeros((max_slots, 2), jnp.uint32)})
        return pools

    if mesh is None:
        return build()
    from jax.sharding import NamedSharding
    specs = paged_cache_specs(cfg, mesh)
    shardings = {n: NamedSharding(mesh, specs[n]) for n in build()}
    return jax.jit(build, out_shardings=shardings)()


def _q8_token(x):
    """Per-token-per-head symmetric int8: scale over the hd axis."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s


def _pages_to_rows(g):
    """Gathered token-major pages (A, P, ps, kvh[, hd]) → contiguous
    head-major rows (A, kvh, P·ps[, hd]), the cache view the attention
    kernels take."""
    return jnp.swapaxes(g.reshape(g.shape[0], -1, *g.shape[3:]), 1, 2)


def _rows_to_pages(a, ps):
    """:func:`_pages_to_rows` backwards: (A, kvh, cap[, hd]) →
    (A, cap/ps, ps, kvh[, hd])."""
    a = jnp.swapaxes(a, 1, 2)
    return a.reshape(a.shape[0], -1, ps, *a.shape[2:])


@jax.named_scope(KV_GATHER_SCOPE)
def _gather_slot_pages(pool, scales, pages_row, dt):
    """One slot's pages → a contiguous (L, kvh, cap, hd) cache view.
    pool: (L, n_pages, ps, kvh, hd); pages_row: (P,) int32, every
    entry in [0, n_pages)."""
    g = pool.at[:, pages_row].get(mode="promise_in_bounds")
    if scales is not None:               # (L, P, ps, kvh, hd) * (.., kvh)
        sc = scales.at[:, pages_row].get(mode="promise_in_bounds")
        g = g.astype(jnp.float32) * sc[..., None]
    return _pages_to_rows(g).astype(dt)


@jax.named_scope(KV_WRITE_SCOPE)
def _write_pages(ck, cv, knew, vnew, layer, phys, off):
    """The new tokens' K/V into the whole (L, n_pages, ps, kvh, hd)
    pools: token i of layer ``layer`` at in-page offset ``off[i]`` of
    page ``phys[i]``. One scatter whose indexed dimensions are the
    pool's leading ones, so the donated (and loop-carried) pool is
    updated in place in the layout it is stored in."""
    ck = ck.at[layer, phys, off].set(knew.astype(ck.dtype))
    cv = cv.at[layer, phys, off].set(vnew.astype(cv.dtype))
    return ck, cv


@jax.named_scope(KV_WRITE_SCOPE)
def _write_pages_q8(ck, cv, cks, cvs, knew, vnew, layer, phys, off):
    """:func:`_write_pages` for an int8 pool: quantise per token, write
    the bytes and their scales."""
    kq, ksc = _q8_token(knew)
    vq, vsc = _q8_token(vnew)
    ck = ck.at[layer, phys, off].set(kq)
    cv = cv.at[layer, phys, off].set(vq)
    cks = cks.at[layer, phys, off].set(ksc)
    cvs = cvs.at[layer, phys, off].set(vsc)
    return ck, cv, cks, cvs


def _pool_head_axis(kvspec):
    """The mesh axis the pool's kv-head dimension (axis 3) is sharded
    over, if any — q/k/v are pinned to it so the write and the gather
    stay local."""
    return kvspec[3] if kvspec is not None and len(kvspec) > 3 else None


def _scan_paged_layers(cfg: LlamaConfig, params, kv, x, layer_fn):
    """Run ``layer_fn(x, lp, layer, *pools) -> (x, *pools)`` over the
    stack with the WHOLE pools in the loop's carry and the layer index
    in ``xs``. Scanned as ``xs``/``ys`` instead, each layer's slab is
    sliced out of the donated pool, rewritten and stacked back: two
    pool-sized copies a step where the write is one token a slot.
    Returns (x, new kv pools)."""
    names = [n for n in ("k", "v", "ks", "vs") if n in kv]

    def body(carry, xs):
        x, pools = carry
        lp, layer = xs
        x, *pools = layer_fn(x, lp, layer, *pools)
        return (x, tuple(pools)), None

    (x, pools), _ = lax.scan(
        body, (x, tuple(kv[n] for n in names)),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return x, dict(zip(names, pools))


def _layer_slots_paged(cfg: LlamaConfig, cos, sin, pos, phys, off,
                       page_table, mesh, kvspec, x, lp, layer, ck, cv,
                       cks=None, cvs=None):
    """One block of the paged slot decode, plain step and speculative
    verify alike: x (S, W, dim) holds each slot's W new tokens (the
    plain step: W = 1); ck/cv are the WHOLE page pools
    (L, n_pages, ps, kvh, hd), reached at ``layer`` by index.

    The index arrays' rank says which step this is, statically. The
    plain step passes ``pos``/``phys``/``off`` of shape (S,): slot s
    scatters its one token's K/V, position ``pos[s]`` of its
    sequence, into pool page ``phys[s]`` at in-page offset ``off[s]``
    and attends ``[0, pos[s] + 1)``. The verify step passes (S, W):
    token i of slot s goes to ``phys[s, i]`` at ``off[s, i]`` and
    attends its OWN causal prefix ``[0, pos[s, i] + 1)`` — the
    per-query length mask that keeps every drafted position's logits
    exactly what a sequential decode at that position would compute.
    The scatter's index shape is the write's own (XLA lays the pool
    out by it), so the plain step never pays for the verify step's.
    The host redirects inactive slots (zeroed table rows) and
    out-of-budget positions to scratch page 0, so no live page can
    alias a write; then each slot attends its gathered pages via the
    length-masked paged kernel."""
    dt = cfg.dtype

    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, lp, h, cos, sin)         # q: (S, h, W, hd)
    head_ax = _pool_head_axis(kvspec)
    q = _mcon(mesh, q, None, head_ax, None, None)
    k = _mcon(mesh, k, None, head_ax, None, None)
    v = _mcon(mesh, v, None, head_ax, None, None)

    if phys.ndim == 1:
        knew, vnew = k[:, :, 0, :], v[:, :, 0, :]        # (S, kvh, hd)
    else:                                # (S, W, kvh, hd)
        knew, vnew = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    if cks is not None:                  # int8 pool: quantize the write
        ck, cv, cks, cvs = _write_pages_q8(ck, cv, cks, cvs, knew,
                                           vnew, layer, phys, off)
        kf = _gather_slot_pages_batch(ck, cks, layer, page_table, dt)
        vf = _gather_slot_pages_batch(cv, cvs, layer, page_table, dt)
        o = slot_decode_attention(q, kf, vf, pos + 1)
    else:
        ck, cv = _write_pages(ck, cv, knew, vnew, layer, phys, off)
        if mesh is not None:
            from jax.sharding import NamedSharding
            ck = lax.with_sharding_constraint(
                ck, NamedSharding(mesh, kvspec))
            cv = lax.with_sharding_constraint(
                cv, NamedSharding(mesh, kvspec))
        o = paged_decode_attention(q, ck, cv, page_table, pos + 1,
                                   layer=layer, mesh=mesh)

    x = x + _mcon(mesh, _out_proj(cfg, lp, o), None, None, None)

    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    delta, _ = _ffn(cfg, lp, h, mesh, serving=True)
    x = x + _mcon(mesh, delta, None, None, None)
    if cks is not None:
        return x, ck, cv, cks, cvs
    return x, ck, cv


def decode_attention_path(cfg: LlamaConfig, kv, mesh: Optional[Mesh] = None,
                          *, verify: bool = False) -> str:
    """Which attention :func:`decode_slots_paged` (``verify``:
    :func:`decode_slots_spec`) builds its program on over the pools
    ``kv`` (arrays or shapes): ``"pages"``, the Pallas kernel that
    reads live pages out of the pool, or ``"gathered"`` —
    ``ops.attention.paged_decode_path``'s answer for what
    :func:`_layer_slots_paged` hands it. Static per compiled program;
    the engine exports it (``serve_decode_steps_total{attention}``,
    ``kv_cache_stats()``)."""
    return paged_decode_path(
        (1, cfg.n_heads, 2 if verify else 1, cfg.head_dim),
        kv["k"].shape, kv["k"].dtype, 2 if verify else 1,
        scales="ks" in kv, mesh=mesh)


@jax.named_scope(KV_GATHER_SCOPE)
def _gather_slot_pages_batch(pool, scales, layer, page_table, dt):
    """All slots' pages of one layer → (S, kvh, cap, hd) with int8
    dequant on the gathered bytes (the whole-pool dequant would undo
    the HBM win). pool is the WHOLE (L, n_pages, ps, kvh, hd) pool;
    page_table is (S, P), every entry in [0, n_pages)."""
    g = pool.at[layer, page_table].get(mode="promise_in_bounds")
    sc = scales.at[layer, page_table].get(mode="promise_in_bounds")
    g = g.astype(jnp.float32) * sc[..., None]     # (S, P, ps, kvh, hd)
    return _pages_to_rows(g).astype(dt)


def decode_slots_paged(cfg: LlamaConfig, params, kv, sv, active,
                       page_table, temperature, top_k, top_p,
                       mesh: Optional[Mesh] = None):
    """ONE continuous-batching decode step over every slot — the
    single compiled program the serving engine keeps hot: per-slot
    position/length arrays drive the RoPE gather, the cache write and
    the length-masked attention, so requests entering and leaving
    never change the program shape (no retraces, ever).

    kv: the pool dict from :func:`init_paged_cache` minus the per-slot
    vectors — the big state, safe to DONATE (the engine does). sv:
    {"lengths", "tokens", "rngs"} — the small per-slot vectors,
    deliberately NOT donated so the engine can overlap the host read
    of one step's tokens with the next step's dispatch. active: (S,)
    bool — inactive slots still flow through (fixed shape) but their
    lengths do not advance and their samples are discarded by the
    engine. ``page_table`` (S, pages_per_slot) int32 is a small
    per-step operand (host-owned: admission edits tables without
    touching device state, and the jit cache key never changes).
    Inactive slots carry zeroed table rows, so their cache write lands
    in scratch page 0 and their (discarded) sample reads scratch —
    active slots' pages are never aliased. temperature/top_k/top_p:
    (S,) per-slot sampling config (traced — a mixed batch shares the
    program). Sampling advances each slot's own rng chain exactly as a
    batch-1 :func:`generate` would (one ``jax.random.split`` per
    emitted token), and the length mask confines a slot to exactly the
    keys ``generate`` attends: that is what makes serving output
    bit-identical to per-request generation (asserted in
    tests/test_paged_kv.py). Returns (sampled (S,) int32, new kv,
    new sv)."""
    ps = kv["k"].shape[2]
    cap = page_table.shape[1] * ps
    lengths = sv["lengths"].astype(jnp.int32)
    pos = jnp.minimum(lengths, cap - 1)       # per-slot write position
    nslots = page_table.shape[0]
    phys = page_table[jnp.arange(nslots), pos // ps]  # (S,) pool index
    off = pos % ps
    x = _embed(cfg, params, sv["tokens"][:, None])

    kvspec = None
    if mesh is not None:
        kvspec = paged_cache_specs(cfg, mesh)["k"]
    cos_t, sin_t = rope_tables(cfg, cap)
    cos = cos_t[pos][:, None, None, :]        # (S, 1, 1, hd/2)
    sin = sin_t[pos][:, None, None, :]

    x, new_kv = _scan_paged_layers(cfg, params, kv, x, partial(
        _layer_slots_paged, cfg, cos, sin, pos, phys, off, page_table,
        mesh, kvspec))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(cfg, params, x)[:, 0]

    new_rngs, sampled = _sample_slots(
        sv["rngs"], logits, temperature, top_k, top_p, mesh)
    new_lengths = lengths + active.astype(jnp.int32)
    if mesh is not None:
        sampled = _mcon(mesh, sampled, None)
        new_lengths = _mcon(mesh, new_lengths, None)
        new_rngs = _mcon(mesh, new_rngs, None, None)
    return sampled, new_kv, \
        {"lengths": new_lengths, "tokens": sampled, "rngs": new_rngs}


@jax.named_scope(KV_WRITE_SCOPE)
def _scatter_slot_pages(kv, pages_row, tmp_k, tmp_v, prefix_len,
                        bucket, int8):
    """Write a slot's contiguous (L, 1, kvh, cap, hd) cache view back
    into the pools at its pages. In f32/bf16 mode the WHOLE view is
    scattered — shared prefix pages are rewritten with bit-identical
    content (the gather/forward never modified them) and duplicate
    scratch indices in ``pages_row`` collapse onto page 0, which is
    never attended. In int8 mode only the freshly written span
    [prefix_len, prefix_len+bucket) is re-quantized; untouched
    positions keep their RAW stored bytes — quantize∘dequant is not
    idempotent, so round-tripping shared pages would corrupt them."""
    cap = tmp_k.shape[3]
    to_pages = partial(_rows_to_pages, ps=kv["k"].shape[2])
    kd, vd = tmp_k[:, 0], tmp_v[:, 0]     # (L, kvh, cap, hd)
    out = dict(kv)
    if int8:
        kq, ksc = _q8_token(kd)           # (L, kvh, cap, hd)/(L,kvh,cap)
        vq, vsc = _q8_token(vd)
        written = ((jnp.arange(cap) >= prefix_len) &
                   (jnp.arange(cap) < prefix_len + bucket))
        old_k = _gather_pages_raw(kv["k"], pages_row)   # (L, kvh, cap, hd)
        old_v = _gather_pages_raw(kv["v"], pages_row)
        old_ks = _gather_pages_raw(kv["ks"], pages_row)
        old_vs = _gather_pages_raw(kv["vs"], pages_row)
        kq = jnp.where(written[None, None, :, None], kq, old_k)
        vq = jnp.where(written[None, None, :, None], vq, old_v)
        ksc = jnp.where(written[None, None, :], ksc, old_ks)
        vsc = jnp.where(written[None, None, :], vsc, old_vs)
        out["k"] = kv["k"].at[:, pages_row].set(to_pages(kq))
        out["v"] = kv["v"].at[:, pages_row].set(to_pages(vq))
        out["ks"] = kv["ks"].at[:, pages_row].set(to_pages(ksc))
        out["vs"] = kv["vs"].at[:, pages_row].set(to_pages(vsc))
    else:
        out["k"] = kv["k"].at[:, pages_row].set(
            to_pages(kd.astype(kv["k"].dtype)))
        out["v"] = kv["v"].at[:, pages_row].set(
            to_pages(vd.astype(kv["v"].dtype)))
    return out


@jax.named_scope(KV_GATHER_SCOPE)
def _gather_pages_raw(pool, pages_row):
    """(L, n_pages, ps, kvh[, hd]) pool → contiguous (L, kvh, cap[,
    hd]) view of one slot's pages, NO dequant (raw stored bytes)."""
    return _pages_to_rows(
        pool.at[:, pages_row].get(mode="promise_in_bounds"))


def prefill_slot_paged(cfg: LlamaConfig, params, tokens, true_len,
                       prefix_len, pages_row, slot, kv, sv, rng,
                       temperature, top_k, top_p,
                       mesh: Optional[Mesh] = None):
    """Admission, cold OR warm: gather the slot's pages into a
    contiguous cache view, run ONE request's SUFFIX tokens (END-padded
    to their bucket) through the cached stack at ``pos=prefix_len``,
    scatter the pages back, seed the slot's length/rng/next-token, and
    sample the first generated token.

    End padding is exact: causal masking means no real position ever
    attends a pad (pads sit after the prompt), pad K/V beyond
    ``true_len`` are excluded by the slot's length mask, and each is
    overwritten by a real decode write before the length ever reaches
    it.

    Warm admission (``prefix_len > 0``) is what prefix sharing buys:
    the shared pages already hold positions [0, prefix_len), the
    suffix attends them through the causal mask exactly as
    ``chunked_prefill`` attends an earlier chunk (the established
    bit-identity property), and only ``len(prompt) - prefix_len``
    tokens pay forward FLOPs — the TTFT win. Cold admission is the
    same program at ``prefix_len=0``. One compiled program per SUFFIX
    bucket (power of two), so compilations are bounded by the bucket
    count no matter what lengths arrive.

    tokens: (1, bucket) suffix; true_len: TOTAL valid length
    (prefix + real suffix); pages_row: (pages_per_slot,) int32 — the
    slot's full table row (scratch-0 tail entries collapse onto the
    never-attended page 0); kv/sv as in :func:`decode_slots_paged`
    (kv donatable). The engine guarantees write range
    [prefix_len, prefix_len+bucket) stays inside the row's capacity
    and that every page it touches is PRIVATE (CoW forked). Returns
    (first token (1,), new kv pools, new sv)."""
    b, bucket = tokens.shape
    int8 = "ks" in kv
    dt = cfg.dtype
    true_len = jnp.asarray(true_len, jnp.int32)
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    tmp = {"k": _gather_slot_pages(kv["k"], kv.get("ks"), pages_row,
                                   dt)[:, None],
           "v": _gather_slot_pages(kv["v"], kv.get("vs"), pages_row,
                                   dt)[:, None],
           "pos": prefix_len}
    logits, tmp = _forward_cached(cfg, params, tokens, tmp, mesh=mesh,
                                  last_index=true_len - prefix_len - 1)
    rng, sub = jax.random.split(rng)
    tok = sample_logits(sub, logits[:, 0], temperature=temperature,
                        top_k=top_k, top_p=top_p, mesh=mesh)
    new_kv = _scatter_slot_pages(kv, pages_row, tmp["k"], tmp["v"],
                                 prefix_len, bucket, int8)
    z = jnp.zeros((), jnp.int32)
    new_sv = {
        "lengths": lax.dynamic_update_slice(
            sv["lengths"].astype(jnp.int32), true_len[None], (slot,)),
        "tokens": lax.dynamic_update_slice(
            sv["tokens"], tok.astype(sv["tokens"].dtype), (slot,)),
        "rngs": lax.dynamic_update_slice(
            sv["rngs"], rng[None].astype(sv["rngs"].dtype), (slot, z)),
    }
    if mesh is not None:
        from jax.sharding import NamedSharding
        specs = paged_cache_specs(cfg, mesh)
        new_kv = {n: lax.with_sharding_constraint(
            a, NamedSharding(mesh, specs[n]))
            for n, a in new_kv.items()}
        new_sv = {n: lax.with_sharding_constraint(
            a, NamedSharding(mesh, specs[n]))
            for n, a in new_sv.items()}
        tok = _mcon(mesh, tok, None)
    return tok, new_kv, new_sv


def inject_paged_kv(cfg: LlamaConfig, k_block, v_block, true_len,
                    pages_row, slot, token, rng, kv, sv,
                    mesh: Optional[Mesh] = None):
    """Decode-side admission of a handed-off prefill: split the
    (L, n_kv_heads, bucket, hd) block into page_size chunks, scatter
    them at the slot's first ceil(bucket/ps) pages and seed the slot's
    length/token/rng — the scatter half of :func:`prefill_slot_paged`,
    with the forward pass already paid on the prefill pool. Pad K/V
    beyond ``true_len`` land in pages the slot owns, are excluded by
    its length mask and overwritten before the length reaches them
    (same argument as bucketed prefill). In int8 mode the block is
    quantized per token on the way in. One compiled program per block
    bucket; kv donatable. Returns (new kv pools, new sv)."""
    int8 = "ks" in kv
    ps = kv["k"].shape[2]
    bucket = k_block.shape[2]
    n_blk = -(-bucket // ps)              # pages the block spans
    pad = n_blk * ps - bucket
    if pad:
        k_block = jnp.pad(k_block, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_block = jnp.pad(v_block, ((0, 0), (0, 0), (0, pad), (0, 0)))
    true_len = jnp.asarray(true_len, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    token = jnp.asarray(token, jnp.int32)
    dst = pages_row[:n_blk]
    to_pages = partial(_rows_to_pages, ps=ps)

    out = dict(kv)
    if int8:
        kq, ksc = _q8_token(k_block)
        vq, vsc = _q8_token(v_block)
        out["k"] = kv["k"].at[:, dst].set(to_pages(kq))
        out["v"] = kv["v"].at[:, dst].set(to_pages(vq))
        out["ks"] = kv["ks"].at[:, dst].set(to_pages(ksc))
        out["vs"] = kv["vs"].at[:, dst].set(to_pages(vsc))
    else:
        out["k"] = kv["k"].at[:, dst].set(
            to_pages(k_block.astype(kv["k"].dtype)))
        out["v"] = kv["v"].at[:, dst].set(
            to_pages(v_block.astype(kv["v"].dtype)))
    z = jnp.zeros((), jnp.int32)
    new_sv = {
        "lengths": lax.dynamic_update_slice(
            sv["lengths"].astype(jnp.int32), true_len[None], (slot,)),
        "tokens": lax.dynamic_update_slice(
            sv["tokens"], token[None].astype(sv["tokens"].dtype),
            (slot,)),
        "rngs": lax.dynamic_update_slice(
            sv["rngs"], rng[None].astype(sv["rngs"].dtype), (slot, z)),
    }
    if mesh is not None:
        from jax.sharding import NamedSharding
        specs = paged_cache_specs(cfg, mesh)
        out = {n: lax.with_sharding_constraint(
            a, NamedSharding(mesh, specs[n])) for n, a in out.items()}
        new_sv = {n: lax.with_sharding_constraint(
            a, NamedSharding(mesh, specs[n]))
            for n, a in new_sv.items()}
    return out, new_sv


def copy_page(kv, src, dst):
    """Copy pool page ``src`` onto page ``dst`` across every pool array
    — the engine's copy-on-write fork primitive (one compiled program
    for any src/dst: both are traced scalars). Only pool arrays (page
    axis 1) are touched; per-slot vectors pass through untouched."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = dict(kv)
    for n in ("k", "v", "ks", "vs"):
        if n in kv:
            a = kv[n]
            page = lax.dynamic_index_in_dim(a, src, axis=1,
                                            keepdims=False)
            out[n] = lax.dynamic_update_index_in_dim(a, page, dst,
                                                     axis=1)
    return out


# ---------------------------------------------------------------------------
# Speculative decoding (ISSUE 19): one batched VERIFY forward over each
# slot's current token plus its k drafted tokens against the paged
# pool, with a bit-exact accept oracle — a drafted token is accepted
# iff it is IDENTICAL to what the target rng chain would emit
# (Leviathan et al. 2023, specialized to exact-match acceptance so the
# served stream is bit-identical to per-request ``generate`` by
# construction, not merely distribution-preserving). Drafting itself is
# host-side (the engine's prompt/n-gram lookup, or a small draft model
# later) — this file only holds the device half.
# ---------------------------------------------------------------------------

def decode_slots_spec(cfg: LlamaConfig, params, kv, sv, active,
                      page_table, drafts, temperature, top_k, top_p,
                      mesh: Optional[Mesh] = None):
    """ONE speculative decode step over the PAGED bank: feed each
    slot's current token plus its ``k`` drafted tokens (W = k + 1
    positions) through a single batched target forward, then run the
    exact-match accept oracle down each slot's rng chain.

    Emission i+1 of a slot is ``sample_logits`` of the logits after
    position pos+i, drawn with the SAME split-discipline as
    :func:`decode_slots_paged` (one ``jax.random.split`` per VALID
    emission — rejected positions never advance the chain, so
    ``serve.resume_key(seed, n_emitted)`` stays exact under
    multi-token emission). Emission i+1 is valid iff every earlier
    draft matched its emission exactly; the number of valid emissions
    per step is therefore 1..W (the plain decode emission always
    lands). Rejected-suffix KV is "rolled back" by simply not
    advancing ``lengths`` past the accepted run: the garbage K/V
    beyond the new length is excluded by every later length mask and
    overwritten in place by the next step's writes — no page is ever
    freed or re-granted mid-run (page refcounts are the host's and
    never change here).

    drafts: (S, k) int32, entry < 0 = no draft at that position (a
    draftless slot emits exactly 1 token, bit-matching the plain
    step). page_table as in :func:`decode_slots_paged` — inactive
    slots carry zeroed rows so all their writes land in scratch page
    0; writes past the table's capacity are redirected to scratch
    rather than clamped (a clamp would corrupt the slot's last live
    page). Returns (toks (S, W) int32, emits (S, W) bool, new kv,
    new sv): the engine emits ``toks[s, :emits[s].sum()]``."""
    ps = kv["k"].shape[2]
    cap = page_table.shape[1] * ps
    S, K = drafts.shape
    W = K + 1
    lengths = sv["lengths"].astype(jnp.int32)
    pos = jnp.minimum(lengths, cap - 1)
    wpos = pos[:, None] + jnp.arange(W)[None, :]      # (S, W)
    oob = wpos >= cap
    cw = jnp.minimum(wpos, cap - 1)                   # safe gather idx
    rows = jnp.arange(S)[:, None]
    phys = jnp.where(oob, 0, page_table[rows, cw // ps])
    off = cw % ps

    toks_in = jnp.concatenate(
        [sv["tokens"][:, None], drafts.astype(sv["tokens"].dtype)],
        axis=1)
    x = _embed(cfg, params, toks_in)

    kvspec = None
    if mesh is not None:
        kvspec = paged_cache_specs(cfg, mesh)["k"]
    cos_t, sin_t = rope_tables(cfg, cap)
    cos = cos_t[cw][:, None]              # (S, 1, W, hd/2)
    sin = sin_t[cw][:, None]

    x, new_kv = _scan_paged_layers(cfg, params, kv, x, partial(
        _layer_slots_paged, cfg, cos, sin, wpos, phys, off,
        page_table, mesh, kvspec))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(cfg, params, x)                 # (S, W, V)

    # accept oracle: scan the W per-position logits down the slot's rng
    # chain. ok carries "all earlier drafts matched"; the key advances
    # ONLY on a valid emission (exactly one split per emitted token).
    nxt = jnp.concatenate(
        [drafts.astype(jnp.int32), jnp.full((S, 1), -1, jnp.int32)],
        axis=1)                           # draft verified by emission i
    has = nxt >= 0

    # every position's two thresholds at once over the (S W, V) block;
    # the scan below only splits and draws
    with jax.named_scope(SAMPLER_SCOPE):
        per_pos = lambda a: jnp.repeat(a, W)
        greedy, masked = _masked_logits(
            logits.reshape(S * W, -1), per_pos(temperature),
            per_pos(top_k), per_pos(top_p), mesh)
        greedy, masked = greedy.reshape(S, W), masked.reshape(S, W, -1)

    def one(key, rows, gr, nx, hs):
        def step(carry, inp):
            key, ok = carry
            row, g, nd, h = inp
            with jax.named_scope(SAMPLER_SCOPE):
                key2, sub = jax.random.split(key)
                tok = _draw(sub, g, row)
            emit = ok
            key = jnp.where(emit, key2, key)
            ok = ok & h & (tok == nd)
            return (key, ok), (tok, emit)
        (key, _), (tk, em) = lax.scan(
            step, (key, jnp.bool_(True)), (rows, gr, nx, hs))
        return key, tk, em

    new_rngs, toks, emits = jax.vmap(one)(
        sv["rngs"], masked, greedy, nxt, has)
    # dtype pinned: under x64 a default integer sum promotes to int64,
    # which would flip the lengths dtype and retrace every program
    n_emit = jnp.sum(emits, axis=1, dtype=jnp.int32)  # (S,) in 1..W
    new_lengths = lengths + n_emit * active.astype(jnp.int32)
    last = jnp.take_along_axis(
        toks, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
    if mesh is not None:
        toks = _mcon(mesh, toks, None, None)
        emits = _mcon(mesh, emits, None, None)
        last = _mcon(mesh, last, None)
        new_lengths = _mcon(mesh, new_lengths, None)
        new_rngs = _mcon(mesh, new_rngs, None, None)
    return toks, emits, new_kv, \
        {"lengths": new_lengths, "tokens": last, "rngs": new_rngs}
