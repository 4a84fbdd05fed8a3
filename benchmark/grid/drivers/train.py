"""The train driver: ``make_train_step`` over ``llama.loss_fn`` on the
mesh the configuration names, stepped on a pool of seeded batches with
the loss read back every step (the fence).

Order of a run (everything before the window is set-up): parameters
made ON the mesh from the seed -> the system's loss against the plain
reference on the check sequences, at the initial parameters (half of
``correct``) -> optimizer state -> warm-up steps (the compile) -> the
window: whole steps until ``seconds`` have passed.
"""
from __future__ import annotations

import json
import time


def run(parts, device, seed, seconds, trace, t_process, log):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu import telemetry
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep
    from mxtpu.parallel.sharding import batch_spec
    from reference import decoder
    import stats
    import trace_reduce
    from program import llama_config, memory_peak, seed_key

    config, job = parts["config"], parts["traffic"]
    run_cfg, check = config["run"], config["check"]
    chips = parts["cell"]["chips"]
    cfg = llama_config(config, run_cfg)
    devices = jax.devices()[:chips]
    mesh = pmesh.create_mesh(devices=devices, **run_cfg["mesh"])
    rules = llama.sharding_rules(cfg)
    if job["optimizer"]["name"] != "adamw":
        raise SystemExit(f"unknown optimizer {job['optimizer']}")
    tx = optax.adamw(job["optimizer"]["learning_rate"])

    # parameters straight onto the mesh: an eager init would put the
    # whole model on device 0 first
    shapes = jax.eval_shape(partial(llama.init_params, cfg), seed_key(seed))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             rules.tree_specs(shapes),
                             is_leaf=lambda s: isinstance(s, P))
    params = jax.jit(partial(llama.init_params, cfg),
                     out_shardings=shardings)(seed_key(seed))

    seq, per_chip = job["seq_len"], job["seqs_per_chip"]
    batch = per_chip * chips
    rng = np.random.default_rng([seed, 21])
    bsharding = NamedSharding(mesh, batch_spec(mesh))
    pool = [{"tokens": jax.device_put(
        rng.integers(0, config["vocab_size"], (batch, seq), np.int32),
        bsharding)} for _ in range(job["batch_pool"])]

    # half of `correct`: the system's loss on the first `n` sequences
    # of the first batch (a mask picks them, so the program keeps the
    # step's own shapes) against the reference on the same sequences
    n = check["n"]
    mask = np.zeros((batch, seq), np.float32)
    mask[:n] = 1.0
    loss_fn = llama.loss_fn(cfg, mesh=mesh)
    t0 = time.monotonic()
    sys_loss = float(jax.jit(loss_fn)(
        params, {"tokens": pool[0]["tokens"],
                 "mask": jax.device_put(mask, bsharding)}))
    ref_loss = float(decoder.loss(
        config, params, jnp.asarray(np.asarray(pool[0]["tokens"])[:n])))
    check_ok = bool(np.isfinite(sys_loss)
                    and abs(sys_loss - ref_loss) <= check["tol"])
    t_check = time.monotonic() - t0

    state = pstep.init_state(params, tx, mesh, rules)
    del params
    train_step = pstep.make_train_step(loss_fn, tx, mesh, rules)

    def compiles():
        return int(telemetry.registry().value("jax_compile_total"))

    losses_warm = []
    for i in range(run_cfg.get("warmup_steps", 2)):
        state, loss = train_step(state, pool[i % len(pool)])
        losses_warm.append(float(jax.device_get(loss)))

    c0 = compiles()
    losses, t_last = [], None
    t_open = time.monotonic()

    def steps_until(t_end):
        """Whole steps, each fenced by its loss, until ``t_end``."""
        nonlocal state, t_last
        while time.monotonic() < t_end:
            k = len(losses_warm) + len(losses)
            state, loss = train_step(state, pool[k % len(pool)])
            losses.append(float(jax.device_get(loss)))
            t_last = time.monotonic()

    if trace:
        # the traced part is whole steps, so that a per-step time is
        # busy time over a count of steps
        with trace_reduce.profiled() as trace_dir:
            steps_until(time.monotonic()
                        + min(trace_reduce.TRACE_S, seconds / 2))
        traced_steps = len(losses)
    steps_until(t_open + seconds)
    c1 = compiles()
    peak = memory_peak(devices)

    win = stats.train_window(losses, t_open, t_last,
                             batch * seq, chips)
    log("# " + json.dumps({
        "check_ok": check_ok, "sys_loss": sys_loss, "ref_loss": ref_loss,
        "check_s": t_check, "loss_first": losses[:4],
        "loss_last": losses[-4:], "warm_losses": losses_warm,
        "steps": len(losses), "step_s": win["step_s"]}))
    obs = {
        "correct": check_ok and win["counts_ok"],
        "attempted": win["attempted"], "failed": win["failed"],
        "memory_peak_bytes": peak,
        "end_to_end": {"train_tok_s": win["train_tok_s"],
                       "setup_s": t_open - t_process},
        "compiles": c1 - c0, "window": win, "config": config,
        "traffic": job, "device": device, "chips": chips,
        "seconds": seconds, "tokens_per_step": batch * seq,
        "batch_per_chip": per_chip,
        "notes": {"check_loss_system": sys_loss,
                  "check_loss_reference": ref_loss,
                  "check_tol": check["tol"], "steps": len(losses),
                  "step_s": win["step_s"]},
    }
    if trace:
        obs["reduced"] = trace_reduce.collect(trace_dir)
        obs["traced_steps"] = traced_steps
    return obs
