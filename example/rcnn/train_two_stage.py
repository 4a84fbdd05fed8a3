#!/usr/bin/env python
"""Two-stage detection, Faster-R-CNN-style (reference ``example/rcnn/``
[path cite — unverified]): the composition no other example exercises —
a REGION PROPOSAL stage whose top-k output feeds an ROIPooling-based
second stage, trained jointly with a multi-term loss in a custom loop.

Stage 1 (RPN): conv backbone → per-anchor objectness + bbox deltas
(anchors from MultiBoxPrior on the feature map). Stage 2: top-k
proposals (static shape — lax-friendly) → ROIPooling on the SHARED
feature map → small head classifying each proposal (3 object classes
+ background).

Synthetic, solvable data: one bright axis-aligned rectangle per image
whose class is its color channel. The final assertion requires the
two-stage pipeline to classify held-out images' best proposal well
above chance — both stages must work for that: the RPN must rank a
box NEAR the object first, and the ROI head must read its class off
the pooled features.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

SMOKE = bool(int(os.environ.get("MXTPU_SMOKE", "0")))
SIZE = 32          # image side
FEAT = 8           # backbone output side (stride 4)
K = 8              # proposals kept per image


def make_batch(rng, n, classes=3):
    """Images (n,3,SIZE,SIZE) + one gt box/class per image."""
    img = rng.normal(0.1, 0.05, (n, 3, SIZE, SIZE)).astype(np.float32)
    boxes = np.zeros((n, 4), np.float32)
    labels = rng.integers(0, classes, n)
    for i in range(n):
        w, h = rng.integers(10, 18, 2)
        x, y = rng.integers(0, SIZE - w), rng.integers(0, SIZE - h)
        img[i, labels[i], y:y + h, x:x + w] += 0.8
        boxes[i] = (x / SIZE, y / SIZE, (x + w) / SIZE, (y + h) / SIZE)
    return np.clip(img, 0, 1), boxes, labels


def iou_anchors(anchors, box):
    """IoU of (A,4) anchors vs one (4,) box, numpy, normalized."""
    ix1 = np.maximum(anchors[:, 0], box[0])
    iy1 = np.maximum(anchors[:, 1], box[1])
    ix2 = np.minimum(anchors[:, 2], box[2])
    iy2 = np.minimum(anchors[:, 3], box[3])
    inter = np.clip(ix2 - ix1, 0, None) * np.clip(iy2 - iy1, 0, None)
    area_a = (anchors[:, 2] - anchors[:, 0]) * \
        (anchors[:, 3] - anchors[:, 1])
    area_b = (box[2] - box[0]) * (box[3] - box[1])
    return inter / (area_a + area_b - inter + 1e-9)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300 if SMOKE else 600)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--lr", type=float, default=2e-3)
    args = p.parse_args()

    import mxtpu as mx
    from mxtpu import autograd, gluon
    from mxtpu.gluon import nn

    rng = np.random.default_rng(0)
    mx.nd.random.seed(0)

    backbone = nn.HybridSequential()
    with backbone.name_scope():
        backbone.add(nn.Conv2D(16, 3, padding=1, activation="relu",
                               in_channels=3),
                     nn.MaxPool2D(2),
                     nn.Conv2D(32, 3, padding=1, activation="relu",
                               in_channels=16),
                     nn.MaxPool2D(2))               # (B,32,FEAT,FEAT)
    rpn = nn.Conv2D(1, 1, in_channels=32)           # objectness/anchor
    head = nn.HybridSequential()
    with head.name_scope():
        head.add(nn.Dense(64, activation="relu",
                          in_units=32 * 3 * 3),
                 nn.Dense(4))                       # 3 classes + bg
    for net in (backbone, rpn, head):
        net.initialize(mx.initializer.Xavier())
        net.hybridize()

    params = {**backbone.collect_params(), **rpn.collect_params(),
              **head.collect_params()}
    trainer = gluon.Trainer(params, "adam",
                            {"learning_rate": args.lr})
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    # one anchor per feature cell (16×16 px at stride 4), normalized
    feat_probe = mx.nd.zeros((1, 32, FEAT, FEAT))
    anchors = mx.nd.contrib.MultiBoxPrior(
        feat_probe, sizes=(0.5,), ratios=(1.0,))[0].asnumpy()  # (A,4)
    A = anchors.shape[0]
    assert A == FEAT * FEAT

    B = args.batch_size
    for step in range(args.steps):
        img, boxes, labels = make_batch(rng, B)
        # anchor targets: positive iff IoU > 0.3 with the gt box
        obj_t = np.stack([(iou_anchors(anchors, boxes[i]) > 0.3)
                          .astype(np.float32) for i in range(B)])
        # proposal class targets come AFTER the forward (they depend
        # on which anchors the RPN ranks top-k), so the loop is two
        # phases — exactly the structure one-stage SSD never needs
        x = mx.nd.array(img)
        with autograd.record():
            feat = backbone(x)
            obj = rpn(feat).reshape((B, A))         # objectness logits
            rpn_loss = bce(obj, mx.nd.array(obj_t)).mean()

            # top-k proposals (static K) — the anchors they index are
            # host-visible, so stage-2 targets assign on the host
            topk = mx.nd.topk(obj.detach(), k=K, axis=1, dtype="int32")
            tk = topk.asnumpy().astype(np.int64)
            rois_np = np.zeros((B * K, 5), np.float32)
            cls_t = np.zeros((B * K,), np.float32)
            for i in range(B):
                sel = anchors[tk[i]]                 # (K,4) normalized
                rois_np[i * K:(i + 1) * K, 0] = i
                rois_np[i * K:(i + 1) * K, 1:] = sel * FEAT
                ious = iou_anchors(sel, boxes[i])
                cls_t[i * K:(i + 1) * K] = np.where(
                    ious > 0.3, labels[i], 3)        # 3 = background
            pooled = mx.nd.contrib.ROIPooling(feat, mx.nd.array(rois_np),
                                      pooled_size=(3, 3),
                                      spatial_scale=1.0)
            scores = head(pooled.reshape((B * K, -1)))
            roi_loss = ce(scores, mx.nd.array(cls_t)).mean()
            loss = rpn_loss + roi_loss
        loss.backward()
        trainer.step(B)
        if step % max(args.steps // 6, 1) == 0:
            print(f"step {step:4d}  rpn {float(rpn_loss.asscalar()):.3f}"
                  f"  roi {float(roi_loss.asscalar()):.3f}")

    # held-out evaluation: classify each image by its BEST proposal
    img, boxes, labels = make_batch(rng, 64)
    feat = backbone(mx.nd.array(img))
    obj = rpn(feat).reshape((64, A))
    best = mx.nd.topk(obj, k=1, axis=1, dtype="int32").asnumpy() \
        .astype(np.int64)[:, 0]
    rois_np = np.zeros((64, 5), np.float32)
    rois_np[:, 0] = np.arange(64)
    rois_np[:, 1:] = anchors[best] * FEAT
    pooled = mx.nd.contrib.ROIPooling(feat, mx.nd.array(rois_np),
                              pooled_size=(3, 3), spatial_scale=1.0)
    pred = head(pooled.reshape((64, -1))).asnumpy()[:, :3].argmax(1)
    acc = float((pred == labels).mean())
    # and the RPN's best proposal must actually cover the object
    hit = np.mean([iou_anchors(anchors[best[i]][None], boxes[i])[0] > 0.2
                   for i in range(64)])
    print(f"proposal hit-rate {hit:.2f}  class acc {acc:.2f}")
    assert hit > 0.6, hit
    assert acc > 0.7, acc
    print("done")


if __name__ == "__main__":
    main()
