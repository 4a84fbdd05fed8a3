"""``drivers/serve_family.py`` with one more check of what no emitted
token shows, ``state`` (``check.also``): the precision a retention
state is HELD in. A state rounded to bf16 after every token moves no
token and no layer's median (the read-outs' operands are bf16 anyway),
so the state a slot holds after a request is held against the plain
reference's sums, through what the window runs: the family's own
``prefill_slot_paged_chunk`` / ``_last`` (the stage, the seating) and
the bank's decode step (``ops.retention.retention_step_bank``, what
``decode_logits`` calls a layer: on a TPU the Pallas kernel) on a bank
of ``run.engine.max_slots`` slots from ``init_paged_cache``, over the
check batch's first sequence, for ONE layer of the stack (the engine's
8-layer bank and weights fill the chip; a layer's state is the same
arithmetic at any depth, and the wiring between layers is the tokens'
to show). Everything else is ``serve_family``'s: this file adds the
entry to its ``ALSO`` and hands on its ``run``."""
from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "grid_drivers_serve_family", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "serve_family.py"))
serve_family = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve_family)

PROBES = 64


def state_check(config, module, cfg, reference, params, seqs, seed, log):
    """The first layer alone, as a model of depth 1 with the stack's
    own weights, as the engine drives it: a decoy prompt (the sequence
    reversed) is seated in slot 0 and in the last slot; the sequence's
    first ``check.prompt_cap`` tokens are prefilled in chunks of
    ``run.engine.prefill_chunk`` through the stage and seated over the
    decoy in the last slot; then ``check.new_tokens`` steps of the whole
    bank fold in the tokens that follow, the last slot alone running.
    That slot's ``(S, z)`` is read through 64 probe queries a KV head,
    ``phi(r)^T S`` and ``phi(r) . z``, and held against ``sum_j G_sj (r
    . k_j)^2 v_j`` and ``sum_j G_sj (r . k_j)^2`` in the attention form
    (``reference.state_readout``: no ``phi``, no state) over the keys,
    values and gates the program's layer was handed (``layer_keys``).

    The steps are handed those same keys: the decode PROGRAM
    (``decode_logits``) is another compilation than ``layer_keys``'
    pass and rounds its bf16 keys at other places (XLA keeps excess
    precision inside a fusion), which alone reads 0.0005-0.0016 here
    whatever type the state is held in (v5e, PR 33), twenty times what
    the state's own rounding does; the prefill programs' keys are
    ``layer_keys``' to the last digit. Returns (ok, notes): the largest
    ``|got - want| / |want|`` (Frobenius) over the KV heads, of the
    numerators and of the denominators, held to ``check.state_tol``;
    and slot 0, which never ran, has to hold its decoy bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dataclasses import replace
    from functools import partial
    from jax import lax
    from mxtpu.ops.retention import retention_step_bank, sympow2
    from program import seed_key
    check, eng = config["check"], config["run"]["engine"]
    chunk, slots = eng["prefill_chunk"], eng["max_slots"]
    n0, n1 = check["prompt_cap"], check["new_tokens"]
    toks = np.asarray(seqs[0][0])[:n0 + n1]
    one = replace(cfg, n_layers=1)
    weights = dict(params, layers=jax.tree.map(lambda a: a[:1],
                                               params["layers"]))
    sv = module.init_paged_cache(one, slots, eng["n_pages"],
                                 eng["page_size"])
    kv = {n: sv.pop(n) for n in ("S", "z")}
    held = kv["S"].dtype
    stage = module.init_prefill_stage(one, eng["max_len"], chunk)
    # the engine's own jits, donations and operands (``_chunk_fn``); the
    # page table's row is the empty one of a pool of no pages, and the
    # first token sampled is not looked at
    first = jax.jit(partial(module.prefill_slot_paged_chunk, one),
                    donate_argnums=(3,))
    last = jax.jit(partial(module.prefill_slot_paged_last, one),
                   donate_argnums=(7,))
    row = np.zeros((0,), np.int32)
    greedy = (jax.random.PRNGKey(0), np.float32(0.0),
              np.int32(cfg.vocab_size), np.float32(1.0))

    def seat(prompt, slot, stage, kv, sv):
        for done in range(0, prompt.size, chunk):
            left = prompt.size - done
            padded = np.zeros((1, chunk), np.int32)
            padded[0, :min(left, chunk)] = prompt[done:done + chunk]
            if left > chunk:
                stage = first(weights, padded, np.int32(done), stage)
            else:
                _, kv, sv = last(weights, padded, np.int32(done),
                                 np.int32(left), stage, row, np.int32(slot),
                                 kv, sv, *greedy)
        return stage, kv, sv

    at = slots - 1
    for prompt, slot in ((toks[::-1][:n0], 0), (toks[::-1][:n0], at),
                         (toks[:n0], at)):
        stage, kv, sv = seat(prompt, slot, stage, kv, sv)
    idle = jax.tree.map(lambda a: a[:, 0] + 0, kv)

    k, v, log_g = (a[0, 0] for a in jax.jit(partial(module.layer_keys, one))(
        weights, jnp.asarray(toks)[None]))          # (s, G, hd), (s, G)
    step = jax.jit(partial(retention_step_bank, layer=0, scale=one.scale),
                   donate_argnums=(4, 5))
    # a slot that is not running: no key, no decay, as the program masks
    alone = lambda a, i: jnp.zeros((slots,) + a.shape[1:], a.dtype).at[
        at].set(a[n0 + i])
    q = jnp.zeros((slots, cfg.n_heads, cfg.head_dim), cfg.dtype)
    for i in range(n1):
        _, kv["S"], kv["z"] = step(q, alone(k, i), alone(v, i),
                                   alone(log_g, i), kv["S"], kv["z"])
    kept = all(bool(jnp.array_equal(kv[n][:, 0], idle[n])) for n in kv)

    probes = jax.random.normal(
        jax.random.fold_in(seed_key(seed), 33), (PROBES, cfg.head_dim),
        jnp.float32)
    hi, f32 = lax.Precision.HIGHEST, jnp.float32
    pr = sympow2(probes)
    num = jnp.einsum("pf,gvf->gpv", pr, kv["S"][0, at].astype(f32),
                     precision=hi)
    den = jnp.einsum("pf,gf->gp", pr, kv["z"][0, at].astype(f32),
                     precision=hi)
    gaps = []
    for g in range(cfg.n_kv_heads):
        want_num, want_den = reference.state_readout(
            probes, k[:, g], v[:, g], log_g[:, g])
        gaps.append(max(
            float(jnp.linalg.norm(num[g] - want_num)
                  / jnp.linalg.norm(want_num)),
            float(jnp.linalg.norm(den[g] - want_den)
                  / jnp.linalg.norm(want_den))))
    gap = max(gaps)
    path = module.decode_attention_path(one, kv)
    log(f"# check state: held in {held}, a bank of {slots} slots, {n0} "
        f"tokens in chunks of {chunk} seated over another prompt's state, "
        f"then {n1} steps ({path}): gap {gap:.3g} (limit "
        f"{check['state_tol']}); the idle slot kept its state: {kept}")
    return kept and gap <= check["state_tol"], {
        "check_state_gap": gap, "check_state_tol": check["state_tol"],
        "check_state_dtype": str(held), "check_state_path": path,
        "check_state_idle_kept": kept}


def run(parts, device, seed, seconds, trace, t_process, log):
    """``serve_family.run``; a traced line also notes the accepted
    ``prefill_chunk_dev_ms`` where the traced seconds saw an admission
    (one window in seven sees none, so the cell is not on that
    metric's list; ``retention_chunk_stall_ms`` reads the whole
    window)."""
    obs = serve_family.run(parts, device, seed, seconds, trace, t_process,
                           log)
    if trace:
        value = serve_family._load(
            "readers", "prefill_chunk_dev_ms.py").read(obs)
        if value is not None:
            obs["notes"]["prefill_chunk_dev_ms"] = float(value)
    return obs


serve_family.ALSO["state"] = state_check
family_of = serve_family.family_of
