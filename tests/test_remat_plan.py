"""``remat_policy=None``: a checkpointed layer keeps, by name, what the
device has room for (``llama.remat_plan``). The plan's arithmetic at the
train cell's widths; the gradients under every rung against full
recomputation; that where the device reports no memory (the CPU, where
all of this runs) the programs are the ones the parent lowered, by
stored hashes of their text; and the record the plan leaves."""
import hashlib
import inspect
import re
from dataclasses import replace
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mxtpu import telemetry
from mxtpu.models import blockdiff_moe, latent_moe, llama, retention, sambay
from mxtpu.parallel import mesh as pmesh, step as pstep

KIB = 1024
# Mistral-7B-v0.3 at eight layers, bf16: the train cell
# (benchmark/grid/configs/mistral-7b-v0.3-d8-train.json), whose four
# chips hold 2 x 4096 tokens each
CELL = llama.LlamaConfig(vocab_size=32768, dim=4096, n_layers=8,
                         n_heads=32, n_kv_heads=8, hidden_dim=14336,
                         max_seq_len=32768, rope_theta=1e6,
                         dtype=jnp.bfloat16)
CELL_TOKENS = 2 * 4096
RUNGS = dict(llama.REMAT_LADDER)
CELL_SEQ = 4096


def _names(*rungs):
    return {n for r in rungs for n in RUNGS[r]}


def _cell_plan(free, **kw):
    return llama.remat_plan(CELL, CELL_TOKENS, free, 1, CELL_SEQ, **kw)


def _spared(plan):
    return sum(ops for r, (_, ops) in llama._rungs(CELL, 1, CELL_SEQ).items()
               if _names(r) <= set(plan))


@pytest.mark.parametrize("rung,bytes_a_token_layer,mflop", [
    ("mlp_gate", 28 * KIB, 117.4),     # 14336 wide
    ("mlp_up", 28 * KIB, 117.4),
    ("attn_out", 8 * KIB + 256, 33.6),  # o; l and m a head: the kernel
    # q 8, k 2, v 2 (before GQA's repeat), the stream after attention 8:
    # the Q, K, V and wo products
    ("attn_qkv", 20 * KIB, 83.9)])
def test_ladder_bytes_and_worth_at_the_cells_widths(
        rung, bytes_a_token_layer, mflop):
    size, worth = llama._rungs(CELL, 1, CELL_SEQ)[rung]
    assert size == bytes_a_token_layer
    assert round(worth / 1e6, 1) == mflop
    # a chip of the cell holds 65,536 token-layers: with exactly the
    # rung's bytes free the plan spares at least what the rung does
    need = bytes_a_token_layer * CELL_TOKENS * CELL.n_layers
    plan, kept = _cell_plan(need)
    assert _spared(plan) >= worth and kept <= need
    assert _cell_plan(8 * KIB * CELL_TOKENS * CELL.n_layers) == ((), 0)


@pytest.mark.parametrize("free", [None, 0, -5])
def test_plan_is_empty_where_nothing_is_free(free):
    assert _cell_plan(free) == ((), 0)


def test_plan_spares_more_for_more_free_bytes_and_never_passes_them():
    rungs = llama._rungs(CELL, 1, CELL_SEQ)
    last = 0
    for free in range(0, 7 << 30, 64 << 20):
        plan, kept = _cell_plan(free)
        assert kept <= free
        assert kept == sum(size for r, (size, _) in rungs.items()
                           if _names(r) <= set(plan)) \
            * CELL_TOKENS * CELL.n_layers
        assert _spared(plan) >= last, (free, plan)
        last = _spared(plan)
    # 84.25 KiB a token-layer in all: 5.65 GB a chip
    assert set(plan) == _names(*RUNGS)
    assert kept == (84 * KIB + 256) * CELL_TOKENS * CELL.n_layers


# what the train cell's chip read when its step was first traced
# (PERF.md §6, PR 37): limit 16,909,334,528, in use 6,041,092,608
CELL_FREE = 4_683_312_888


def test_the_cells_plan_and_the_room_around_it():
    """Both MLP products and the kernel's output (64.25 KiB a
    token-layer, 4.31 GB; 268 MFLOP a token-layer spared). The sums a
    plan can take lie apart, so the plan holds for 0.37 GB less free
    (a check's arrays not yet freed, a prefetch buffer, the profiler)
    and 0.41 GB more: another plan is another program and a cold
    compile."""
    want = _names("mlp_gate", "mlp_up", "attn_out")
    for free in (CELL_FREE, CELL_FREE - 370_000_000,
                 CELL_FREE + 410_000_000):
        plan, kept = _cell_plan(free)
        assert set(plan) == want and kept == 4_311_744_512
    assert set(_cell_plan(CELL_FREE - 380_000_000)[0]) \
        == _names("mlp_gate", "mlp_up")
    assert set(_cell_plan(CELL_FREE + 420_000_000)[0]) \
        == _names("mlp_gate", "mlp_up", "attn_qkv")


# ms a step on the cell's four chips, one process, the plan the code's
# own or forced (PERF.md §6, PR 37, review round: my chip run). A set of
# equal operations that is never picked, ``mlp_gate, attn_out,
# attn_qkv``, read 896.11 beside ``mlp_gate, mlp_up``
CELL_READ_MS = {
    (): 968.54,
    ("mlp_gate", "mlp_up"): 897.15,
    ("mlp_gate", "mlp_up", "attn_out"): 876.71,
}


def test_more_free_bytes_never_picks_a_set_that_read_slower_on_the_chip():
    read = []
    for free in range(0, 7 << 30, 16 << 20):
        picked = tuple(r for r in RUNGS
                       if _names(r) <= set(_cell_plan(free)[0]))
        if picked in CELL_READ_MS and CELL_READ_MS[picked] not in read:
            read.append(CELL_READ_MS[picked])
    assert read == sorted(CELL_READ_MS.values(), reverse=True)


def test_plan_divides_the_widths_over_tp_and_skips_what_is_not_there():
    whole, half = llama._rungs(CELL), llama._rungs(CELL, tp=2)
    for rung in ("mlp_gate", "mlp_up", "attn_out"):
        assert half[rung][0] * 2 == whole[rung][0]
    # q, k, v over tp; the stream is whole on every device
    assert half["attn_qkv"][0] == (12 * KIB) // 2 + 8 * KIB
    moe = replace(CELL, moe_experts=8)
    assert set(llama.remat_plan(moe, CELL_TOKENS, 1 << 40, 1,
                                CELL_SEQ)[0]) == _names(
        "attn_out", "attn_qkv")
    # only the Pallas kernel names its output and statistics: on any
    # other attention path the rung is not offered, so its bytes are
    # never spent on nothing
    for free in range(0, 7 << 30, 256 << 20):
        plan, _ = _cell_plan(free, flash_kernel=False)
        assert not _names("attn_out") & set(plan)
    assert set(_cell_plan(1 << 40, flash_kernel=False)[0]) == _names(
        "mlp_gate", "mlp_up", "attn_qkv")


# ---------------------------------------------------------------------------
# the same gradients, whatever is kept
# ---------------------------------------------------------------------------
TOY = replace(llama.CONFIGS["tiny"], remat=True, dtype=jnp.float32)
TOY_TOKENS = 2 * 32


def _force(monkeypatch, *rungs):
    """Make the plan ``rungs``, whatever the device reports."""
    names = tuple(n for r, ns in llama.REMAT_LADDER if r in rungs
                  for n in ns)
    monkeypatch.setattr(llama, "remat_plan", lambda *a: (names, 0))


def _grads(cfg, accum):
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (accum, 2, 32), 0,
                                cfg.vocab_size)
    loss = llama.loss_fn(cfg)

    def total(p):
        return sum(loss(p, {"tokens": t}) for t in tokens) / accum
    return jax.jit(jax.value_and_grad(total))(params)


_FULL = {}      # (scan, accum) -> loss and gradients, every layer recomputed


@pytest.mark.parametrize("scan,accum", [(True, 1), (False, 1), (True, 2)],
                         ids=["scan", "unrolled", "accum2"])
@pytest.mark.parametrize("rungs", [("mlp_gate",), ("mlp_up",),
                                   ("attn_out",), ("attn_qkv",),
                                   ("mlp_gate", "mlp_up", "attn_out"),
                                   tuple(RUNGS)],
                         ids=lambda r: "all" if len(r) > 3 else "+".join(r))
def test_gradients_equal_full_recomputation(monkeypatch, rungs, scan,
                                            accum):
    cfg = replace(TOY, scan_layers=scan)
    if (scan, accum) not in _FULL:      # once a mode: the CPU's own plan
        _FULL[scan, accum] = _grads(cfg, accum)
    want_loss, want = _FULL[scan, accum]
    _force(monkeypatch, *rungs)
    got_loss, got = _grads(cfg, accum)
    assert float(got_loss) == float(want_loss)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)


def test_train_step_grad_accum_runs_under_a_plan(monkeypatch):
    """``make_train_step``'s own accumulation scan with the plan inside
    it: the first loss equals full recomputation's."""
    cfg = replace(TOY, scan_layers=True)
    losses = []
    for rungs in ((), tuple(RUNGS)):
        _force(monkeypatch, *rungs)
        step, state = _train_step(cfg, accum=2)
        _, loss = step(state, {"tokens": jnp.ones((2, 2, 32), jnp.int32)})
        losses.append(float(loss))
    assert abs(losses[0] - losses[1]) <= 1e-6


# ---------------------------------------------------------------------------
# the record
# ---------------------------------------------------------------------------
def _train_step(cfg, accum=1):
    mesh = pmesh.create_mesh(devices=jax.devices()[:1], fsdp=1)
    rules = llama.sharding_rules(cfg)
    tx = optax.adamw(3e-4)
    state = pstep.init_state(
        llama.init_params(cfg, jax.random.PRNGKey(0)), tx, mesh, rules)
    step = pstep.make_train_step(llama.loss_fn(cfg, mesh=mesh), tx, mesh,
                                 rules, grad_accum=accum)
    return step, state


class _Reports:
    """A device that reports its memory: 16 GiB, 6 in use."""
    def memory_stats(self):
        return {"bytes_limit": 16 << 30, "bytes_in_use": 6 << 30}


def test_plan_goes_to_the_gauge_and_the_programs_record(monkeypatch):
    cfg = replace(TOY, scan_layers=True)
    rungs = llama._rungs(cfg)
    free = (rungs["mlp_gate"][0] + rungs["mlp_up"][0]) * TOY_TOKENS \
        * cfg.n_layers
    monkeypatch.setattr(llama, "_free_bytes", lambda *a: free)
    step, state = _train_step(cfg)
    step(state, {"tokens": jnp.ones((2, 32), jnp.int32)})
    assert telemetry.registry().value("train_remat_saved_bytes") == free
    prog = telemetry.programs()["train_step"]
    assert prog.remat_plan == ("mlp_gate", "mlp_up")
    assert prog.remat_saved_bytes == free


def test_cpu_reports_no_memory_so_a_train_steps_plan_is_empty():
    cfg = replace(TOY, scan_layers=True)
    assert jax.devices()[0].memory_stats() is None
    telemetry.registry().gauge("train_remat_saved_bytes", "").set(7)
    step, state = _train_step(cfg)
    step(state, {"tokens": jnp.ones((2, 32), jnp.int32)})
    assert telemetry.registry().value("train_remat_saved_bytes") == 0
    assert telemetry.programs()["train_step"].remat_plan == ()


@pytest.mark.parametrize("trace", ["forward", "grad"])
def test_no_plan_and_no_record_outside_a_train_step(monkeypatch, trace):
    """A forward pass (the benchmark's check is ``jit(loss_fn)``) and a
    ``jax.grad`` of one's own are traced with nothing known of the state
    the device will hold: even on a device that reports memory the layer
    is recomputed whole, and neither the gauge nor a program's record
    hears of a plan that no train step runs."""
    cfg = replace(TOY, scan_layers=True)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Reports()])
    params = jax.eval_shape(partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    assert pstep.traced_state_bytes() is None
    assert llama._free_bytes(cfg, params, TOY_TOKENS, None) is None
    telemetry.registry().gauge("train_remat_saved_bytes", "").set(7)
    plans = []
    real = llama.remat_plan
    monkeypatch.setattr(llama, "remat_plan", lambda *a: plans.append(
        real(*a)) or plans[-1])
    fn = llama.loss_fn(cfg)
    jax.eval_shape(fn if trace == "forward" else jax.grad(fn), params,
                   {"tokens": jnp.ones((2, 32), jnp.int32)})
    assert plans == [((), 0)]
    assert telemetry.registry().value("train_remat_saved_bytes") == 7


@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "described"])
def test_a_train_step_counts_its_state_from_shapes(monkeypatch, resident):
    """``traced_state_bytes`` while the step is traced: the parameters
    and AdamW's two moments a device, by the rule table, the same
    whether the step is called with arrays or lowered from shapes."""
    cfg = replace(TOY, scan_layers=True)
    seen = []
    real = llama._free_bytes
    monkeypatch.setattr(llama, "_free_bytes", lambda *a: seen.append(
        pstep.traced_state_bytes()) or real(*a))
    step, state = _train_step(cfg)
    batch = {"tokens": jnp.ones((2, 32), jnp.int32)}
    if resident:
        step(state, batch)
    else:
        described = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), state)
        step._jitted.lower(described, batch, None)
    weights = sum(p.size * 4 for p in jax.tree.leaves(state.params))
    # weights, mu, nu; optax's count and the step, an int32 each
    assert seen == [3 * weights + 4 + 4]
    assert pstep.traced_state_bytes() is None


def test_free_bytes_takes_state_gradients_and_reserve_off_the_limit(
        monkeypatch):
    """A device that reports: 16 GiB, 6 in use. What is free is the rest
    less 8% of the limit and the reserve, whose gradients are the
    parameters' bytes on that device (over fsdp); where the step's state
    by its shapes is more than what lies on the device (a state only
    described, or not yet all there), the state is what is taken off."""
    cfg = replace(TOY, scan_layers=True)
    params = jax.eval_shape(partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    mesh = SimpleNamespace(devices=np.array([_Reports()], dtype=object),
                           shape={"dp": 1, "fsdp": 4, "sp": 1, "tp": 1})
    monkeypatch.setattr(jax, "devices", lambda *a: [_Reports()])
    clear = (16 << 30) - (16 << 30) // 100 * 8
    for state in (0, 6 << 30, 9 << 30):
        monkeypatch.setattr(llama, "traced_state_bytes", lambda: state)
        for m in (None, mesh):
            assert llama._free_bytes(cfg, params, TOY_TOKENS, m) \
                == clear - max(6 << 30, state) \
                - llama._step_reserve(cfg, params, TOY_TOKENS, m)
    # the reserve's parts on one device, and the gradients over fsdp
    whole = sum(p.size * 4 for p in jax.tree.leaves(params))
    norms = sum(p.size * 4 for k, p in
                jax.tree_util.tree_flatten_with_path(params)[0]
                if "norm" in jax.tree_util.keystr(k))
    layer = sum(p.size for p in jax.tree.leaves(params["layers"])) \
        // cfg.n_layers
    rest = TOY_TOKENS * cfg.n_layers * cfg.dim * 4 + 2 * layer * 4 \
        + 2 * TOY_TOKENS * cfg.vocab_size * 4
    assert llama._step_reserve(cfg, params, TOY_TOKENS, None) \
        == whole + layer * cfg.n_layers * 4 + rest
    assert llama._step_reserve(cfg, params, TOY_TOKENS, mesh) \
        == norms + (whole - norms) // 4 \
        + layer * cfg.n_layers // 4 * 4 + rest


def test_unknown_policy_is_refused():
    cfg = replace(TOY, remat_policy="everything")
    with pytest.raises(ValueError, match="unknown remat_policy"):
        llama.forward_hidden(cfg, llama.init_params(cfg),
                             jnp.ones((1, 8), jnp.int32))


# ---------------------------------------------------------------------------
# the programs the parent lowered
# ---------------------------------------------------------------------------
def _canon(text):
    """Lowered text with the private functions' serial numbers
    (``@_where_159``: a count kept by the process, so another history
    gives other numbers) renumbered by first appearance."""
    seen = {}
    return re.sub(r"@(\w+?)_(\d+)\b", lambda m: "@%s#%d" % (
        m.group(1), seen.setdefault(m.group(0), len(seen))), text)


def _hash(lowered):
    return hashlib.sha256(_canon(lowered.as_text()).encode()) \
        .hexdigest()[:16]


SLOTS, PAGE, N_PAGES, BUCKET = 4, 16, 32, 32
FAMILIES = {"llama": llama, "sambay": sambay, "latent_moe": latent_moe,
            "retention": retention, "blockdiff_moe": blockdiff_moe}


def _serve_program(family, name):
    cfg = family.CONFIGS["tiny"]
    S = jax.ShapeDtypeStruct
    scalar = partial(S, ())
    params = jax.eval_shape(partial(family.init_params, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda: family.init_paged_cache(cfg, SLOTS, N_PAGES, PAGE))
    per_slot = getattr(family, "SLOT_VARS", ("lengths", "tokens", "rngs"))
    kv = {n: a for n, a in state.items() if n not in per_slot}
    sv = {n: state[n] for n in per_slot}
    pages = cfg.max_seq_len // PAGE
    if name != "prefill_slot_paged":
        return jax.jit(partial(getattr(family, name), cfg),
                       donate_argnums=(1,)).lower(
            params, kv, sv, S((SLOTS,), jnp.bool_),
            S((SLOTS, pages), jnp.int32), S((SLOTS,), jnp.float32),
            S((SLOTS,), jnp.int32), S((SLOTS,), jnp.float32))
    return jax.jit(partial(family.prefill_slot_paged, cfg),
                   donate_argnums=(6,)).lower(
        params, S((1, BUCKET), jnp.int32), scalar(jnp.int32),
        scalar(jnp.int32), S((pages,), jnp.int32), scalar(jnp.int32), kv,
        sv, S((2,), jnp.uint32), scalar(jnp.float32), scalar(jnp.int32),
        scalar(jnp.float32))


# of the parent's text (commit a222295), under this directory's conftest
SERVE_HASHES = {
    ("llama", "decode_slots_paged"): "1447a3cf408000b1",
    ("llama", "prefill_slot_paged"): "9010ee692861b62f",
    ("sambay", "decode_slots_paged"): "323d962ef33d64b1",
    ("sambay", "prefill_slot_paged"): "4aafbb1fd6259039",
    ("latent_moe", "decode_slots_paged"): "59f23ba6168c2b6f",
    ("latent_moe", "prefill_slot_paged"): "b4025e6b5d5f672c",
    ("retention", "decode_slots_paged"): "76b59a7516b5d713",
    ("retention", "prefill_slot_paged"): "3434601c8dd32d68",
    # of commit 641b7c7, before the expert bank learned a non-gated form
    ("blockdiff_moe", "block_step_slots_paged"): "b169c62c572e1515",
    ("blockdiff_moe", "prefill_slot_paged"): "3ac2427f15c0ba0a",
}


@pytest.mark.parametrize("family,program", list(SERVE_HASHES))
def test_serve_programs_lower_to_the_parents_text(family, program):
    """A name outside a checkpoint is an identity that leaves no
    operation: the serve programs that trace ``_qkv``, ``_out_proj`` and
    ``_ffn`` (llama's, and retention's and latent_moe's, which borrow
    them) and ``flash_attention`` (sambay's prefill) are unchanged."""
    assert _hash(_serve_program(FAMILIES[family], program)) \
        == SERVE_HASHES[family, program]


def _lowered_train_step(scan, accum):
    cfg = replace(llama.CONFIGS["tiny"], remat=True, scan_layers=scan)
    step, state = _train_step(cfg, accum)
    shape = (accum, 2, 64) if accum > 1 else (2, 64)
    return step._jitted.lower(state, {"tokens": jnp.zeros(shape, jnp.int32)},
                              None)


TRAIN_HASHES = {(True, 1): "0068e398ffb51ec2",
                (False, 1): "583870d591e51e68",
                (True, 2): "21cb4253fa3c8986"}


@pytest.mark.parametrize("scan,accum", list(TRAIN_HASHES),
                         ids=["scan", "unrolled", "accum2"])
def test_train_step_on_the_cpu_lowers_to_the_parents_text(scan, accum):
    """No memory reported, no plan: ``remat_policy=None`` is plain
    ``jax.checkpoint(layer)``, the parent's program text for text."""
    assert _hash(_lowered_train_step(scan, accum)) \
        == TRAIN_HASHES[scan, accum]


def test_the_librarys_flash_kernels_are_where_the_backward_rule_calls_them():
    """``ops.attention._pallas_flash`` calls three private functions of
    the installed jax by position and keyword: pinned here."""
    from jax.experimental.pallas.ops.tpu import flash_attention as lib
    impl = list(inspect.signature(lib._flash_attention_impl).parameters)
    assert impl == ["q", "k", "v", "ab", "segment_ids", "save_residuals",
                    "causal", "sm_scale", "block_b", "block_q",
                    "block_k_major", "block_k", "debug"]
    for fn, extra in ((lib._flash_attention_bwd_dkv, ["block_q"]),
                      (lib._flash_attention_bwd_dq, [])):
        sig = inspect.signature(fn).parameters
        assert list(sig)[:9] == ["q", "k", "v", "ab", "segment_ids", "l",
                                 "m", "do", "di"]
        assert {"block_q_major", "block_k_major", "block_k", "sm_scale",
                "causal", "mask_value", "debug", *extra} <= set(sig)
    assert isinstance(lib.DEFAULT_MASK_VALUE, float)
