#!/usr/bin/env python
"""BERT masked-LM pretraining (BASELINE config 3 recipe): synthetic
corpus when no data given; full jitted sharded train step (dp on one
chip; dp×tp×fsdp on a pod via the same code path)."""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def synth_batch(rng, cfg, batch, seq, n_pred):
    import jax.numpy as jnp
    tokens = rng.integers(4, cfg.vocab_size, (batch, seq))
    pos = np.stack([rng.choice(seq, n_pred, replace=False)
                    for _ in range(batch)])
    labels = np.take_along_axis(tokens, pos, axis=1)
    masked = tokens.copy()
    np.put_along_axis(masked, pos, 3, axis=1)     # [MASK]=3
    return {"tokens": jnp.asarray(masked, jnp.int32),
            "mask": jnp.ones((batch, seq), jnp.float32),
            "mlm_positions": jnp.asarray(pos, jnp.int32),
            "mlm_labels": jnp.asarray(labels, jnp.int32),
            "mlm_weights": jnp.ones(pos.shape, jnp.float32),
            "nsp_labels": jnp.asarray(
                rng.integers(0, 2, (batch,)), jnp.int32)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="tiny",
                   choices=["tiny", "bert_base", "bert_large"])
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--bench", action="store_true",
                   help="synthetic-data throughput run")
    args = p.parse_args()

    import jax
    import optax
    from mxtpu.models import bert
    from mxtpu.parallel import mesh as pmesh, step as pstep

    cfg = bert.CONFIGS[args.config]
    if args.seq_len > cfg.max_seq_len:
        print(f"clamping seq-len {args.seq_len} -> {cfg.max_seq_len} "
              f"({args.config}'s position table)")
        args.seq_len = cfg.max_seq_len
    mesh = pmesh.create_mesh(dp=-1)
    rules = bert.sharding_rules(cfg)
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(args.lr, weight_decay=0.01)
    state = pstep.init_state(params, tx, mesh, rules)
    step = pstep.make_train_step(bert.loss_fn(cfg), tx, mesh, rules)

    rng = np.random.default_rng(0)
    n_pred = max(1, args.seq_len // 7)
    batch = synth_batch(rng, cfg, args.batch_size, args.seq_len, n_pred)
    state, loss = step(state, batch)          # compile
    print(f"initial loss {float(loss):.4f}")
    t0 = time.time()
    for i in range(args.steps):
        if not args.bench:
            batch = synth_batch(rng, cfg, args.batch_size, args.seq_len,
                                n_pred)
        state, loss = step(state, batch)
    float(jax.device_get(loss))    # read-back: the steps are done
    dt = time.time() - t0
    print(f"final loss {float(loss):.4f}")
    print(f"{args.batch_size * args.steps / dt:.1f} samples/s "
          f"({dt / args.steps * 1e3:.1f} ms/step)")


if __name__ == "__main__":
    main()
