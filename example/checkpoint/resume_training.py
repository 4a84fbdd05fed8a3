#!/usr/bin/env python
"""Checkpoint / resume training (reference ``mx.callback.do_checkpoint``
+ ``Module.fit(begin_epoch=k)`` restart-from-latest recovery [path
cites — unverified]): the orbax-backed manager on a sharded TrainState.

The demo trains a sharded tiny llama, checkpointing every step with
retention; "crashes" (drops the live state); resumes from the latest
COMMITTED checkpoint into a fresh process-state; and proves the
resumed trajectory lands exactly where an uninterrupted run would.

Run: python example/checkpoint/resume_training.py   (any device count)
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    import optax
    from dataclasses import replace
    from mxtpu import checkpoint as ckpt
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep

    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                  attn_impl="dense", remat=False)
    n = len(jax.devices())
    if n % 4 == 0 and n >= 4:
        mesh, rows = pmesh.create_mesh(fsdp=2, tp=2), 4
    else:
        # pure-dp fallback: the batch must divide over all n devices
        mesh, rows = pmesh.create_mesh(dp=-1), (4 if 4 % n == 0 else n)
    rules = llama.sharding_rules(cfg)
    tx = optax.adamw(1e-3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (rows, 32)), jnp.int32)
    step = pstep.make_train_step(llama.loss_fn(cfg), tx, mesh, rules)

    def fresh_state(seed):
        return pstep.init_state(
            llama.init_params(cfg, jax.random.PRNGKey(seed)),
            tx, mesh, rules)

    ckdir = os.path.join(tempfile.mkdtemp(), "ck")
    mgr = ckpt.CheckpointManager(ckdir, max_to_keep=3,
                                 async_save=False)

    # ---- run A: train 6 steps, checkpoint each, then "crash" --------
    state = fresh_state(0)
    losses = []
    for i in range(6):
        state, loss = step(state, {"tokens": tokens})
        mgr.save(i, state)
        losses.append(float(jax.device_get(loss)))
    mgr.wait_until_finished()
    print(f"ran 6 steps, checkpoints kept: {mgr.all_steps()} "
          f"(retention 3)", flush=True)
    del state                                # the "crash"

    # ---- run B: resume from latest into a FRESH abstract state ------
    latest = mgr.latest_step()
    assert latest == 5
    restored = mgr.restore(abstract_state=fresh_state(99))
    print(f"resumed from step {latest}; restored step counter = "
          f"{int(restored.step)}", flush=True)
    # params really landed on the live mesh with rule-table shardings
    wq = restored.params["layers"]["wq"]
    print("wq sharding:", wq.sharding.spec)

    resumed = []
    state = restored
    for i in range(6, 10):
        state, loss = step(state, {"tokens": tokens})
        resumed.append(float(jax.device_get(loss)))

    # ---- ground truth: the uninterrupted run ------------------------
    ref_state = fresh_state(0)
    ref = []
    for i in range(10):
        ref_state, loss = step(ref_state, {"tokens": tokens})
        ref.append(float(jax.device_get(loss)))

    np.testing.assert_allclose(losses, ref[:6], rtol=1e-6)
    np.testing.assert_allclose(resumed, ref[6:], rtol=1e-6)
    print("resumed losses == uninterrupted losses "
          f"({[round(v, 4) for v in resumed]})")
    mgr.close()
    print("checkpoint example OK")


if __name__ == "__main__":
    main()
