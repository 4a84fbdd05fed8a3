#!/usr/bin/env python
"""Input-pipeline throughput: the native ImageRecordIter decode path
(reference ``src/io/iter_image_recordio_2.cc`` — the reference treated
input throughput as a first-class perf surface, ``docs/faq/perf.md``
[path cites — unverified]).

Measures, on a generated JPEG .rec, the portable host work apart
from this machine's host-to-device link:

  * host decode capacity: drain the C++ pipeline directly, NO jax —
    the number that transfers to any host (img/s per decode core)
  * component costs: RecordIO read alone, JPEG decode alone
  * H2D link bandwidth, each upload fenced by a scalar read-back that
    depends on it
  * delivered-to-device rate: the full ImageRecordIter, fenced the
    same way — what a training loop on THIS machine actually receives
  * the pure-Python ImageIter path for contrast

The last three put arrays on the device, so they need the chip: run
the script as a process of its own for them. ``--host-only`` stops
before them — that is how ``bench.py`` calls it, because a process
that already holds the chip cannot lend it to a child.

Prints ONE JSON line.

Usage: python benchmark/input_bench.py [--n 600] [--size 256] [--out 224]
                                       [--host-only]
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def make_rec(path, n, size, quality=95):
    """Synthetic photographic-ish JPEGs (smooth gradients + noise so
    jpeg entropy/decoding cost is realistic, not flat-field trivial)."""
    from mxtpu import recordio
    rng = np.random.default_rng(0)
    w = recordio.MXIndexedRecordIO(
        os.path.splitext(path)[0] + ".idx", path, "w")
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    for i in range(n):
        base = (127 + 100 * np.sin(6.28 * (xx * (1 + i % 5) +
                                           yy * (1 + i % 3))))
        img = np.stack([base, base[::-1], base.T], axis=-1)
        img = img + rng.normal(0, 12, img.shape)
        img = np.clip(img, 0, 255).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0), img,
            quality=quality))
    w.close()
    return path


def time_raw_pipe(rec, out, batch_size, threads, min_seconds):
    """Host decode capacity: C++ pipeline drained directly (u8 mode),
    no jax anywhere — pure host-side img/s."""
    from mxtpu.native import NativePipeline
    pipe = NativePipeline(rec, out, out, 3, False, 0, threads,
                          out_u8=True)
    n, t0 = 0, time.perf_counter()
    done = False
    while not done:
        while True:
            d, _ = pipe.next_batch(batch_size)
            if len(d) == 0:
                pipe.reset()
                break
            n += len(d)
            if time.perf_counter() - t0 >= min_seconds:
                done = True
                break
    rate = n / (time.perf_counter() - t0)
    pipe.close()
    return rate


def fence(batch):
    """Device fence: a scalar read-back DEPENDENT on the batch
    (asnumpy would time a 38MB D2H no training loop does)."""
    return float(batch.data[0][0, 0, 0, 0].asscalar())


def time_iter_fenced(it, min_seconds):
    """Delivered-to-device img/s: drain the full iterator, scalar-
    fencing the last batch of every epoch so queued device work can't
    masquerade as throughput."""
    n, t0 = 0, time.perf_counter()
    done = False
    while not done:
        it.reset()
        batch = None
        for batch in it:
            n += batch.data[0].shape[0] - batch.pad
            if time.perf_counter() - t0 >= min_seconds:
                done = True
                break
        if batch is not None:
            fence(batch)
    return n / (time.perf_counter() - t0)


def measure_h2d(shape_bytes=(64, 224, 224, 3), reps=4):
    """Fenced host→device bandwidth for a u8 batch (MB/s)."""
    import jax
    import jax.numpy as jnp
    x = np.random.default_rng(0).integers(
        0, 255, shape_bytes).astype(np.uint8)
    probe = jax.jit(lambda a: a[0, 0, 0, 0].astype(jnp.float32))
    float(probe(jax.device_put(x)))            # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        float(probe(jax.device_put(x)))        # fenced upload
    dt = (time.perf_counter() - t0) / reps
    return x.nbytes / dt / 1e6, dt * 1000


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=600)
    p.add_argument("--size", type=int, default=256)
    p.add_argument("--out", type=int, default=224)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--host-only", action="store_true",
                   help="stop before the legs that put data on the "
                        "device")
    args = p.parse_args()

    from mxtpu import native, recordio

    tmp = tempfile.mkdtemp()
    rec = make_rec(os.path.join(tmp, "bench.rec"), args.n, args.size)
    rec_bytes = os.path.getsize(rec)

    results = {}

    # component: RecordIO read alone (native reader, no decode)
    rd = native.NativeRecordReader(rec)
    t0 = time.perf_counter()
    reads = 0
    while time.perf_counter() - t0 < 1.0:
        for i in range(len(rd)):
            rd.read(i)
        reads += len(rd)
    results["recordio_read_img_s"] = round(
        reads / (time.perf_counter() - t0), 1)

    # component: JPEG decode alone (single-thread, native)
    raw = [recordio.unpack(rd.read(i))[1]
           for i in range(min(64, args.n))]
    rd.close()
    from mxtpu.native import jpeg_decode
    t0 = time.perf_counter()
    dec = 0
    while time.perf_counter() - t0 < 1.0:
        for buf in raw:
            jpeg_decode(buf)
            dec += 1
    results["jpeg_decode_img_s_1thread"] = round(
        dec / (time.perf_counter() - t0), 1)

    # host decode CAPACITY (no jax), worker-scaled — the portable number
    for threads in (1, 2, 4):
        results[f"host_decode_img_s_{threads}thread"] = round(
            time_raw_pipe(rec, args.out, args.batch_size, threads,
                          args.seconds), 1)

    if not args.host_only:
        device_legs(args, rec, results)

    results["rec_mb"] = round(rec_bytes / 1e6, 1)
    results["ncpu"] = os.cpu_count()
    best = max(v for k, v in results.items()
               if k.startswith("host_decode"))
    print(json.dumps({
        "metric": "input_host_decode_img_s_per_core",
        "value": best, "unit": "img/s",
        "vs_baseline": None, "extra": results}))
    return 0


def device_legs(args, rec, results):
    """The legs that upload: H2D bandwidth, the full iterator with and
    without the prefetcher, and the Python path for contrast."""
    from mxtpu import io as mio

    mbs, ms = measure_h2d((args.batch_size, args.out, args.out, 3))
    results["h2d_u8_mb_s_fenced"] = round(mbs, 1)
    results["h2d_u8_ms_per_batch"] = round(ms, 1)

    # delivered-to-device rate through the full iterator, fenced
    shape = (3, args.out, args.out)
    it = mio.ImageRecordIter(
        path_imgrec=rec, data_shape=shape,
        batch_size=args.batch_size, shuffle=False, preprocess_threads=2)
    assert type(it).__name__ == "NativeImageRecordIter", type(it)
    time_iter_fenced(it, 0.5)                  # warm up + compile
    results["delivered_to_device_img_s"] = round(
        time_iter_fenced(it, args.seconds), 1)
    it.close()

    # same leg behind the double-buffered DevicePrefetcher: decode +
    # dispatch move to a background thread, so the upload of batch k+1
    # overlaps the consumer's work on batch k (docs/perf.md prefetch-
    # overlap subsection; same scalar fence)
    from mxtpu.gluon.data import DevicePrefetcher
    it = mio.ImageRecordIter(
        path_imgrec=rec, data_shape=shape,
        batch_size=args.batch_size, shuffle=False, preprocess_threads=2)
    pf = DevicePrefetcher(it)
    time_iter_fenced(pf, 0.5)                  # warm up + compile
    results["prefetched_delivered_img_s"] = round(
        time_iter_fenced(pf, args.seconds), 1)
    pf.close()

    # contrast: the Python ImageIter path (force it via an aug flag).
    # batch 8: at ~3 img/s a 64-image batch holds the prefetch worker
    # in TF decode for ~20 s, which close() would have to wait out
    it = mio.ImageRecordIter(
        path_imgrec=rec, data_shape=shape, batch_size=8,
        shuffle=False, rand_mirror=True)
    results["python_imageiter_img_s"] = round(
        time_iter_fenced(it, min(args.seconds, 2.0)), 1)
    it.close()


if __name__ == "__main__":
    sys.exit(main())
