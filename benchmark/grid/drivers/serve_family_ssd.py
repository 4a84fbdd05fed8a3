"""``drivers/serve_family.py`` with one more check of what no emitted
token shows, ``state`` (``check.also``): the precision a Mamba-2 state is
HELD and carried in, read from the engine's OWN bank. A state rounded to
bf16 at every chunk and step moves no token and no layer's median over
the check batch, so the state a served slot holds is held against the
plain reference's recurrence, a token at a time
(``reference.ssm_scan``).

After the check batch, with the engine idle and every slot free, ONE
greedy request more (the check batch's first prompt, ``check.new_tokens``
tokens) goes through the gateway. The engine seats it in its first slot,
over the state a check request left there, and runs it through the
programs the window times: the prompt in chunks of
``run.engine.prefill_chunk`` (``prefill_slot_paged_chunk`` / ``_last``:
the chunked SSD, the stage, the seating), then ``decode_slots_paged``,
the whole model over the whole bank, a step a token. The slot's state in
the FIRST layer of the stack, a Mamba-2 layer whose state depends on the
tokens alone, is read from the bank (``ServeEngine.device_state``).
Everything else is ``serve_family``'s: this file hands on its ``run``
with the engine and the gateway's address kept for the check
(``_SERVING``), and adds the entry to its ``ALSO``."""
from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "grid_drivers_serve_family", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "serve_family.py"))
serve_family = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve_family)

# what the ``state`` check needs besides an ``ALSO`` entry's arguments,
# for the length of a run: the engine the gateway built ("engine"), and,
# while the check batch runs, the client and the gateway's address
_SERVING: dict = {}


def _gap(got, want):
    """The largest ``|got - want| / |want|`` (Frobenius) over the heads
    of a (H, p, n) state."""
    import jax.numpy as jnp
    got = got.astype(jnp.float32)
    num = jnp.sqrt(((got - want) ** 2).sum((1, 2)))
    return float((num / jnp.sqrt((want ** 2).sum((1, 2)))).max())


def state_check(config, module, cfg, reference, params, seqs, seed, log):
    """The served slot's first-layer state against the reference's
    recurrence over the tokens the engine folded in: the request's prompt
    and emitted tokens, as many as the device's ``lengths`` says (a
    finished request's slot may run one inert step more). The reference
    is handed the inputs the program's first layer computes for them
    (``ssm_inputs``, a chunk at a time at the chunk programs' shapes:
    the same x, dt, B, C, so that the projections' rounding in another
    program is not read as the state's). Returns (ok, notes): the largest
    ``|got - want| / |want|`` over the heads, held to ``check.state_tol``;
    and the slots that did not run have to keep every layer's state bit
    for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dataclasses import replace
    from functools import partial
    del seqs, seed
    check, eng = config["check"], config["run"]["engine"]
    chunk, n1 = eng["prefill_chunk"], check["new_tokens"]
    engine = _SERVING["engine"]
    job = _SERVING["traffic"].check_batch(1, check["prompt_cap"], n1)[0]
    job["id"] = "state"
    n0, slot = len(job["prompt"]), 0

    bank = engine.device_state()
    held = bank["ssm"].dtype
    idle = {n: bank[n][:, slot + 1:] + 0 for n in ("conv", "ssm")}
    rec = _SERVING["serve"].client({
        "mode": "batch", "host": _SERVING["host"], "port": _SERVING["port"],
        "jobs": [job], "together": True})[0]
    if (rec["status"] != 200 or rec["reason"] != "complete"
            or len(rec["tokens"]) != n1):
        log(f"# check state: the request came back {rec['status']} "
            f"{rec['reason']} {rec['error']}")
        return False, {"check_state_gap": float("nan")}
    bank = engine.device_state()
    got = bank["ssm"][0, slot] + 0
    n = int(bank["lengths"][slot])
    kept = all(bool(jnp.array_equal(bank[k][:, slot + 1:], a))
               for k, a in idle.items())
    del idle
    if not n0 + n1 - 1 <= n <= n0 + n1:
        log(f"# check state: slot {slot} holds {n} tokens, not the "
            f"request's {n0} + {n1 - 1} or {n1}")
        return False, {"check_state_gap": float("nan"),
                       "check_state_tokens": n}

    one = replace(cfg, pattern="M")
    toks = np.asarray(job["prompt"] + rec["tokens"], np.int32)[:n]
    inputs = jax.jit(partial(module.ssm_inputs, one))
    stage = module.init_prefill_stage(one, eng["max_len"], chunk)
    parts = []
    for done in range(0, n, chunk):
        m = min(chunk, n - done)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :m] = toks[done:done + m]
        out, stage = inputs(params, padded, np.int32(done), np.int32(m),
                            stage)
        parts.append([a[0, :m] for a in out])
    x, dt, B, C = (jnp.concatenate(a) for a in zip(*parts))
    A = -jnp.exp(params["mamba"]["A_log"][0].astype(jnp.float32))
    want, _ = jax.jit(reference.ssm_scan)(x, dt, A, B, C)
    gap = _gap(got, want)
    log(f"# check state: held in {held}, slot {slot} of a bank of "
        f"{eng['max_slots']} after {n0} tokens in chunks of {chunk} and "
        f"{n - n0} decode steps: gap {gap:.3g}, limit {check['state_tol']};"
        f" the idle slots kept their state: {kept}")
    return kept and gap <= check["state_tol"], {
        "check_state_gap": gap, "check_state_tol": check["state_tol"],
        "check_state_tokens": n, "check_state_steps": n - n0,
        "check_state_dtype": str(held), "check_state_idle_kept": kept}


_check_batch = serve_family.check_batch


def check_batch(serve, config, module, cfg, reference, params, traffic,
                host, port, log):
    """``serve_family.check_batch``, with the client, the gateway's
    address and the traffic kept where ``state_check`` finds them."""
    _SERVING.update(serve=serve, host=host, port=port, traffic=traffic)
    try:
        return _check_batch(serve, config, module, cfg, reference, params,
                            traffic, host, port, log)
    finally:
        for k in ("serve", "host", "port", "traffic"):
            _SERVING.pop(k, None)


def run(parts, device, seed, seconds, trace, t_process, log):
    """``serve_family.run``, the engine its gateway makes kept in
    ``_SERVING`` (``serve_family.run`` takes ``ServeEngine`` from
    ``mxtpu.serve`` when it is called, so for the run that name is bound
    to a function that keeps the engine it makes)."""
    import mxtpu.serve as serving
    built = serving.ServeEngine

    def keep(*args, **kw):
        _SERVING["engine"] = built(*args, **kw)
        return _SERVING["engine"]
    serving.ServeEngine = keep
    try:
        return serve_family.run(parts, device, seed, seconds, trace,
                                t_process, log)
    finally:
        serving.ServeEngine = built
        _SERVING.clear()


serve_family.ALSO["state"] = state_check
serve_family.check_batch = check_batch
family_of = serve_family.family_of
