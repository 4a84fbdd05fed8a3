#!/usr/bin/env python
"""SSD-style single-shot detection training (reference
``example/ssd/`` [path cite — unverified]), end to end on synthetic
data: ImageDetIter over a packed detection RecordIO → conv backbone →
MultiBoxPrior anchors → per-anchor class + box heads → MultiBoxTarget
(with hard-negative mining) → softmax-CE + smooth-L1 loss →
MultiBoxDetection (decode + NMS) evaluation.

The dataset is solvable by construction: each image is a noisy
background with 1-3 axis-aligned bright rectangles whose CLASS is its
color channel — so a few epochs must lift the detection hit rate well
above chance, which the final assertion checks.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def synth_det_rec(path, n=48, hw=32, seed=0):
    """Pack n synthetic detection images: label [2, 5, (cls,x1,y1,x2,y2)*]."""
    from mxtpu import recordio
    rng = np.random.default_rng(seed)
    w = recordio.MXIndexedRecordIO(path.replace(".rec", ".idx"),
                                   path, "w")
    for i in range(n):
        img = (rng.random((hw, hw, 3)) * 60).astype(np.uint8)
        boxes = []
        for _ in range(int(rng.integers(1, 4))):
            cls = int(rng.integers(0, 3))
            bw, bh = rng.uniform(0.25, 0.45, 2)
            x1 = rng.uniform(0.0, 1.0 - bw)
            y1 = rng.uniform(0.0, 1.0 - bh)
            px = (np.array([x1, y1, x1 + bw, y1 + bh]) * hw).astype(int)
            img[px[1]:px[3], px[0]:px[2], cls] = 230   # color == class
            boxes.append([float(cls), x1, y1, x1 + bw, y1 + bh])
        label = [2.0, 5.0] + [v for b in boxes for v in b]
        hdr = recordio.IRHeader(0, np.array(label, np.float32), i, 0)
        w.write_idx(i, recordio.pack_img(hdr, img, quality=95))
    w.close()
    return path


def build_net(num_cls, n_anchors):
    from mxtpu.gluon import nn
    net = nn.HybridSequential()
    for ch in (16, 32, 32):                  # 32 -> 16 -> 8 -> 4
        net.add(nn.Conv2D(ch, 3, strides=2, padding=1, use_bias=False),
                nn.BatchNorm(), nn.Activation("relu"))
    # heads stay convolutional (SSD): one 3x3 conv each
    cls_head = nn.Conv2D(n_anchors * (num_cls + 1), 3, padding=1)
    loc_head = nn.Conv2D(n_anchors * 4, 3, padding=1)
    return net, cls_head, loc_head


def forward(net, cls_head, loc_head, x, num_cls, n_anchors):
    import mxtpu as mx
    feat = net(x)                            # (B, C, 4, 4)
    B = x.shape[0]
    cp = cls_head(feat)                      # (B, A*(cls+1), 4, 4)
    lp = loc_head(feat)
    # (B, H, W, A, cls+1) -> (B, anchors, cls+1)
    cp = cp.transpose((0, 2, 3, 1)).reshape(
        (B, -1, num_cls + 1))
    lp = lp.transpose((0, 2, 3, 1)).reshape((B, -1))
    anchors = mx.nd.contrib.MultiBoxPrior(
        feat, sizes=(0.35, 0.5), ratios=(1.0, 2.0, 0.5), clip=True)
    return feat, cp, lp, anchors


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=12)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--lr", type=float, default=0.02)
    args = p.parse_args()
    import mxtpu as mx
    from mxtpu import autograd, gluon
    from mxtpu.image import ImageDetIter

    num_cls, n_anchors = 3, 4                # sizes(2)+ratios(3)-1
    rec = synth_det_rec(os.path.join(tempfile.mkdtemp(), "det.rec"))
    it = ImageDetIter(batch_size=args.batch_size,
                      data_shape=(3, 32, 32), path_imgrec=rec,
                      shuffle=True)

    net, cls_head, loc_head = build_net(num_cls, n_anchors)
    for blk in (net, cls_head, loc_head):
        blk.initialize()
    params = {}
    for blk in (net, cls_head, loc_head):
        params.update(blk.collect_params())
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9,
                             "wd": 1e-4})

    for epoch in range(args.epochs):
        it.reset()
        tot, nb = 0.0, 0
        for batch in it:
            x, label = batch.data[0], batch.label[0]
            with autograd.record():
                _, cp, lp, anchors = forward(
                    net, cls_head, loc_head, x, num_cls, n_anchors)
                cls_pred_t = cp.transpose((0, 2, 1))  # (B, cls+1, A)
                loc_t, loc_mask, cls_t = mx.nd.contrib.MultiBoxTarget(
                    anchors, label, cls_pred_t,
                    negative_mining_ratio=3.0)
                logp = mx.nd.log_softmax(cp, axis=-1)
                picked = mx.nd.pick(logp, mx.nd.relu(cls_t), axis=2)
                keep = (cls_t >= 0)                   # -1 = ignore
                n_pos = mx.nd.maximum(loc_mask.sum() / 4.0,
                                      mx.nd.ones((1,)))
                cls_loss = -(picked * keep).sum() / n_pos
                loc_loss = (mx.nd.smooth_l1(
                    (lp - loc_t) * loc_mask, scalar=1.0)).sum() / n_pos
                loss = cls_loss + loc_loss
            loss.backward()
            trainer.step(1)
            tot += float(loss.asscalar())
            nb += 1
        print(f"epoch {epoch}: loss {tot / nb:.4f}", flush=True)

    # evaluation: decode + NMS, count images whose best detection hits
    # a ground-truth box of the right class at IoU >= 0.5
    it.reset()
    hits = total = 0
    for batch in it:
        x, label = batch.data[0], batch.label[0]
        _, cp, lp, anchors = forward(net, cls_head, loc_head, x,
                                     num_cls, n_anchors)
        cls_prob = mx.nd.softmax(cp, axis=-1).transpose((0, 2, 1))
        dets = mx.nd.contrib.MultiBoxDetection(
            cls_prob, lp, anchors, nms_threshold=0.45,
            threshold=0.1).asnumpy()
        lab = label.asnumpy()
        for b in range(dets.shape[0]):
            gt = lab[b][lab[b, :, 0] >= 0]
            valid = dets[b][dets[b, :, 0] >= 0]
            total += 1
            if not len(valid):
                continue
            best = valid[np.argmax(valid[:, 1])]
            for g in gt:
                ix1, iy1 = np.maximum(best[2:4], g[1:3])
                ix2, iy2 = np.minimum(best[4:6], g[3:5])
                inter = max(ix2 - ix1, 0) * max(iy2 - iy1, 0)
                a1 = (best[4] - best[2]) * (best[5] - best[3])
                a2 = (g[3] - g[1]) * (g[4] - g[2])
                iou = inter / max(a1 + a2 - inter, 1e-9)
                if iou >= 0.5 and int(best[0]) == int(g[0]):
                    hits += 1
                    break
    rate = hits / max(total, 1)
    print(f"detection hit rate: {rate:.2f} ({hits}/{total})")
    assert rate >= 0.5, f"SSD failed to learn (hit rate {rate:.2f})"
    print("ssd example OK")


if __name__ == "__main__":
    main()
