#!/usr/bin/env python
"""Mixed-precision training (reference ``example`` AMP usage +
``python/mxnet/contrib/amp/`` [path cites — unverified]), both AMP
modes on one small conv net:

1. **bfloat16** (the TPU-native default): ``amp.init("bfloat16")`` +
   ``convert_hybrid_block`` casts params (normalization layers stay
   f32); bf16 shares f32's exponent range so the scaler is static and
   no per-step overflow sync exists at all.
2. **float16 + dynamic loss scaling**, on the one-program fused path:
   ``Trainer.make_fused_step`` folds the scaled backward, the global
   isfinite overflow decision, and skip-update-on-overflow INTO the
   compiled step — scaler state lives on device, no host round-trip.

Both runs must reach the f32 baseline's accuracy on a synthetic
blob-classification task.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def make_blobs(n=512, seed=0):
    """4-class 'images': each class lights up one quadrant."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 4, n)
    x = rng.standard_normal((n, 1, 8, 8)).astype(np.float32) * 0.3
    for i, c in enumerate(y):
        r, cq = divmod(int(c), 2)
        x[i, 0, r * 4:(r + 1) * 4, cq * 4:(cq + 1) * 4] += 1.0
    return x, y.astype(np.float32)


def build_net(amp_cast_after_bn=False):
    from mxtpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.BatchNorm())                  # stays f32 under AMP
    if amp_cast_after_bn:
        # the reference's low_precision_pass inserted amp_cast nodes
        # around fp32-island ops; here one explicit cast re-enters the
        # half-precision stream after the f32 BatchNorm
        from mxtpu import amp
        net.add(nn.HybridLambda(
            lambda F, x: amp.amp_cast(x, "bfloat16")))
    net.add(nn.MaxPool2D(2),
            nn.Dense(32, activation="relu"),
            nn.Dense(4))
    net.initialize()
    return net


def accuracy(net, X, Y, dtype="float32"):
    import mxtpu as mx
    out = net(mx.nd.array(X).astype(dtype)).asnumpy()
    return float((out.argmax(1) == Y).mean())


def run_f32(X, Y, epochs):
    import mxtpu as mx
    from mxtpu import autograd, gluon
    net = build_net()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    xb, yb = mx.nd.array(X), mx.nd.array(Y)
    for _ in range(epochs):
        with autograd.record():
            out = net(xb)
            loss = mx.nd.softmax_cross_entropy(out, yb) / X.shape[0]
        loss.backward()
        tr.step(1)
    return accuracy(net, X, Y)


def run_bf16(X, Y, epochs):
    """Classic loop in bfloat16: cast params once, train as usual —
    no scaler machinery needed on TPU's native half type."""
    import mxtpu as mx
    from mxtpu import amp, autograd, gluon
    amp.init("bfloat16")
    net = amp.convert_hybrid_block(build_net(amp_cast_after_bn=True))
    # BatchNorm params stayed f32 (the reference's fp32 deny list)
    dtypes = {p.name: p.dtype for p in net.collect_params().values()}
    assert any(str(d) == "bfloat16" for d in dtypes.values())
    assert all("batchnorm" not in n or str(d) == "float32"
               for n, d in dtypes.items())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    amp.init_trainer(tr)        # static scaler: bf16 needs no scaling
    xb = mx.nd.array(X).astype("bfloat16")
    yb = mx.nd.array(Y)
    for _ in range(epochs):
        with autograd.record():
            out = net(xb)
            loss = mx.nd.softmax_cross_entropy(
                out.astype("float32"), yb) / X.shape[0]
            with amp.scale_loss(loss, tr) as scaled:
                pass
        scaled.backward()
        tr.step(1)
    return accuracy(net, X, Y, dtype="bfloat16")


def run_fp16_fused(X, Y, epochs):
    """float16-style dynamic scaling on the fused one-program path:
    overflow detection, skip, and the scale schedule all compile into
    the train step."""
    import mxtpu as mx
    from mxtpu import amp, gluon
    from mxtpu.parallel import mesh as pmesh
    from mxtpu.parallel.sharding import P, ShardingRules

    amp.init("float16")
    net = build_net()
    net(mx.nd.array(X[:2]))     # resolve deferred shapes before shard
    net.hybridize()
    net.shard(pmesh.create_mesh(dp=-1), ShardingRules([(r".*", P())]))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    amp.init_trainer(tr)
    xb, yb = mx.nd.array(X), mx.nd.array(Y)
    fused = tr.make_fused_step(
        net, loss_fn=lambda out: mx.nd.softmax_cross_entropy(out, yb)
        / X.shape[0])
    for _ in range(epochs):
        fused(xb)
    print(f"  fused AMP: scale {fused.loss_scale():.1f}, "
          f"applied {fused.applied_updates()}/{epochs} updates, "
          f"{fused.num_compiles()} compiled program(s)")
    return accuracy(net, X, Y)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=40)
    args = p.parse_args()
    X, Y = make_blobs()
    acc_f32 = run_f32(X, Y, args.epochs)
    print(f"f32 baseline acc: {acc_f32:.3f}", flush=True)
    acc_bf16 = run_bf16(X, Y, args.epochs)
    print(f"bf16 AMP acc: {acc_bf16:.3f}", flush=True)
    acc_fp16 = run_fp16_fused(X, Y, args.epochs)
    print(f"fp16 fused dynamic-scaling acc: {acc_fp16:.3f}", flush=True)
    for name, acc in (("bf16", acc_bf16), ("fp16-fused", acc_fp16)):
        assert acc > 0.9 and acc > acc_f32 - 0.1, \
            f"{name} AMP failed to match f32 ({acc} vs {acc_f32})"
    print("amp example OK")


if __name__ == "__main__":
    main()
