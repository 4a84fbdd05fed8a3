#!/usr/bin/env python
"""Matrix-factorization recommender (reference ``example/recommenders``
[path cite — unverified]): two Embedding tables trained jointly so
their dot product predicts ratings — the classic sparse-interaction
workload (each step touches only the rows in the batch; on TPU the
gather/scatter rides XLA while the batched dot stays on the MXU).

Synthetic, solvable target: ratings come from a ground-truth low-rank
model (user/item factors + biases + noise). Training must drive test
RMSE well below the all-mean predictor and close to the noise floor —
asserted at the end.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

SMOKE = bool(int(os.environ.get("MXTPU_SMOKE", "0")))


def make_ratings(rng, n_users, n_items, rank, n_obs, noise=0.1):
    u = rng.normal(0, 0.5, (n_users, rank)).astype(np.float32)
    v = rng.normal(0, 0.5, (n_items, rank)).astype(np.float32)
    bu = rng.normal(0, 0.2, n_users).astype(np.float32)
    bi = rng.normal(0, 0.2, n_items).astype(np.float32)
    ui = rng.integers(0, n_users, n_obs)
    ii = rng.integers(0, n_items, n_obs)
    r = (3.0 + (u[ui] * v[ii]).sum(1) + bu[ui] + bi[ii] +
         rng.normal(0, noise, n_obs)).astype(np.float32)
    return ui.astype(np.float32), ii.astype(np.float32), r


def make_model(nn, HybridBlock, n_users, n_items, rank):
    class MatrixFact(HybridBlock):
        """Hybridized so each training step is ONE compiled program —
        eager per-op dispatch dominates this tiny model's step time."""

        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.user_emb = nn.Embedding(n_users, rank)
                self.item_emb = nn.Embedding(n_items, rank)
                self.user_bias = nn.Embedding(n_users, 1)
                self.item_bias = nn.Embedding(n_items, 1)

        def hybrid_forward(self, F, users, items):
            p = (self.user_emb(users) * self.item_emb(items)).sum(
                axis=-1, keepdims=True)
            return (p + self.user_bias(users) + self.item_bias(items)
                    + 3.0).squeeze(axis=-1)

    return MatrixFact()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--users", type=int, default=150 if SMOKE else 800)
    p.add_argument("--items", type=int, default=200 if SMOKE else 1000)
    p.add_argument("--rank", type=int, default=8)
    p.add_argument("--obs", type=int, default=12000 if SMOKE else 80000)
    p.add_argument("--epochs", type=int, default=12 if SMOKE else 20)
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--wd", type=float, default=1e-5)
    args = p.parse_args()

    import mxtpu as mx
    from mxtpu import gluon, nd
    from mxtpu.gluon import nn

    rng = np.random.default_rng(7)
    ui, ii, r = make_ratings(rng, args.users, args.items, args.rank,
                             args.obs)
    n_test = args.obs // 10
    test = (ui[:n_test], ii[:n_test], r[:n_test])
    train = (ui[n_test:], ii[n_test:], r[n_test:])

    from mxtpu.gluon import HybridBlock
    from mxtpu.parallel import mesh as pmesh
    from mxtpu.parallel.sharding import ShardingRules, P

    model = make_model(nn, HybridBlock, args.users, args.items,
                       args.rank)
    model.initialize(init=mx.initializer.Normal(0.1))
    model.hybridize()
    model(nd.array(train[0][:args.batch_size]),
          nd.array(train[1][:args.batch_size]))  # resolve shapes
    mesh = pmesh.create_mesh(dp=-1)
    model.shard(mesh, ShardingRules([(r".*", P())]))
    trainer = gluon.Trainer(model.collect_params(), "adam",
                            {"learning_rate": args.lr,
                             "wd": args.wd})
    l2 = gluon.loss.L2Loss()
    # the recommended one-program path: forward + backward + Adam in a
    # single donated XLA program, not per-op eager dispatch
    step = trainer.make_fused_step(
        model, loss_fn=lambda out, y: l2(out, y).mean(), loss_args=1)

    def rmse(split):
        su, si, sr = split
        pred = model(nd.array(su), nd.array(si)).asnumpy()
        return float(np.sqrt(np.mean((pred - sr) ** 2)))

    base = float(np.sqrt(np.mean((test[2] - train[2].mean()) ** 2)))
    n = len(train[0])
    for epoch in range(args.epochs):
        perm = rng.permutation(n)
        last = None
        for s in range(0, n - args.batch_size + 1, args.batch_size):
            idx = perm[s:s + args.batch_size]
            last = step(nd.array(train[0][idx]),
                        nd.array(train[1][idx]),
                        nd.array(train[2][idx]))  # async
        if epoch % 4 == 0 or epoch == args.epochs - 1:
            print(f"epoch {epoch}: last batch loss "
                  f"{float(last.asscalar()):.4f}, "
                  f"test rmse {rmse(test):.4f} (baseline {base:.4f})")

    final = rmse(test)
    print(f"final test rmse {final:.4f} vs mean-predictor {base:.4f}")
    assert final < 0.6 * base, (final, base)
    print("matrix-fact OK")


if __name__ == "__main__":
    main()
