#!/usr/bin/env python
"""DCGAN-style adversarial training (reference ``example/gluon/dcgan``
[path cite — unverified]): the composition pattern nothing else in
example/ exercises — TWO networks, TWO optimizers, and a custom
alternating update loop where each step trains one net on the other's
output.

Synthetic, solvable target: "real" images are a dark background with a
bright centered square (+noise). After training, the generator's
samples must reproduce that structure — center brightness well above
border brightness — which the final assertion checks. The
discriminator trains on real-vs-fake with label smoothing; the
generator trains through the discriminator (autograd flows through
BOTH nets, but only G's Trainer steps).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

SMOKE = bool(int(os.environ.get("MXTPU_SMOKE", "0")))


def real_batch(rng, n, size=16):
    img = rng.normal(0.1, 0.05, (n, 1, size, size)).astype(np.float32)
    q = size // 4
    img[:, :, q:-q, q:-q] += 0.8
    return np.clip(img, 0.0, 1.0)


def build_nets(nn, latent):
    gen = nn.HybridSequential()
    with gen.name_scope():
        gen.add(nn.Dense(128, activation="relu", in_units=latent),
                nn.Dense(4 * 4 * 16, activation="relu"),
                nn.HybridLambda(lambda F, x: x.reshape((-1, 16, 4, 4))),
                nn.Conv2DTranspose(8, 4, strides=2, padding=1,
                                   activation="relu", in_channels=16),
                nn.Conv2DTranspose(1, 4, strides=2, padding=1,
                                   activation="sigmoid", in_channels=8))
    disc = nn.HybridSequential()
    with disc.name_scope():
        disc.add(nn.Conv2D(8, 3, strides=2, padding=1,
                           activation="relu", in_channels=1),
                 nn.Conv2D(16, 3, strides=2, padding=1,
                           activation="relu", in_channels=8),
                 nn.Flatten(),
                 nn.Dense(1))
    return gen, disc


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=120 if SMOKE else 600)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--latent", type=int, default=16)
    p.add_argument("--lr", type=float, default=2e-3)
    args = p.parse_args()

    import mxtpu as mx
    from mxtpu import autograd, gluon
    from mxtpu.gluon import nn

    rng = np.random.default_rng(0)
    mx.nd.random.seed(0)
    gen, disc = build_nets(nn, args.latent)
    gen.initialize(mx.initializer.Xavier())
    disc.initialize(mx.initializer.Xavier())
    gen.hybridize()
    disc.hybridize()

    # TWO optimizers — adversarial training steps them alternately
    tr_g = gluon.Trainer(gen.collect_params(), "adam",
                         {"learning_rate": args.lr, "beta1": 0.5})
    tr_d = gluon.Trainer(disc.collect_params(), "adam",
                         {"learning_rate": args.lr, "beta1": 0.5})
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()

    B = args.batch_size
    ones = mx.nd.ones((B, 1))
    zeros = mx.nd.zeros((B, 1))
    smooth = ones * 0.9                  # one-sided label smoothing
    for step in range(args.steps):
        real = mx.nd.array(real_batch(rng, B))
        z = mx.nd.array(rng.standard_normal((B, args.latent))
                        .astype(np.float32))

        # D step: real→1 (smoothed), G(z)→0. G's params get no grads
        # written back because only tr_d steps.
        with autograd.record():
            fake = gen(z)
            d_loss = (bce(disc(real), smooth).mean() +
                      bce(disc(fake.detach()), zeros).mean())
        d_loss.backward()
        tr_d.step(B)

        # G step: make D call G(z) real — gradients flow THROUGH D
        # into G; only tr_g steps, so D stays fixed this half-step
        with autograd.record():
            g_loss = bce(disc(gen(z)), ones).mean()
        g_loss.backward()
        tr_g.step(B)

        if step % max(args.steps // 6, 1) == 0:
            print(f"step {step:4d}  d_loss {float(d_loss.asscalar()):.3f}"
                  f"  g_loss {float(g_loss.asscalar()):.3f}")

    # the generator must have learned the structure: bright center,
    # dark border (compare against the real data's own contrast)
    z = mx.nd.array(rng.standard_normal((64, args.latent))
                    .astype(np.float32))
    samples = gen(z).asnumpy()
    q = samples.shape[-1] // 4
    center = samples[:, :, q:-q, q:-q].mean()
    border = (samples.sum() - samples[:, :, q:-q, q:-q].sum()) / (
        samples.size - samples[:, :, q:-q, q:-q].size)
    print(f"generated center {center:.3f} vs border {border:.3f}")
    assert center > border + 0.3, (center, border)
    print("done")


if __name__ == "__main__":
    main()
