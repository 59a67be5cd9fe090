#!/usr/bin/env python
"""SSD detection training on synthetic shapes — driver config 4
(ref: example/ssd/train.py, example/ssd/train/train_net.py).

End-to-end through the contrib detection ops: MultiBoxPrior anchors
over multi-scale feature maps, conv cls/loc heads, MultiBoxTarget
assignment with hard-negative mining, SmoothL1 + cross-entropy
losses through the fused gluon Trainer, and MultiBoxDetection NMS at
eval with a real (numpy-oracle) VOC-style mAP gate.

The dataset is synthetic (zero egress): each image carries one solid
bright rectangle; class = rectangle orientation (wide/tall).  --quick
is the CI gate (100 steps of batch 16 on 64 images of 48x48, <1 min
CPU).  --anchor-scale-check additionally runs
target assignment + NMS once at the reference's full SSD300 anchor
count (8732) to exercise the kernels at real scale.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

# runnable from anywhere: put the repo root on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="synthetic SSD training")
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--num-iters", type=int, default=150)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--num-images", type=int, default=128)
    p.add_argument("--quick", action="store_true")
    p.add_argument("--anchor-scale-check", action="store_true",
                   help="also run target+NMS once at SSD300's 8732 "
                   "anchors")
    return p.parse_args(argv)


NUM_CLASSES = 2  # wide / tall rectangles (+ background id 0)


def make_dataset(rs, n, size):
    """Images (n,3,size,size) with one bright axis-aligned rectangle;
    labels (n,1,5) rows [class_id, xmin, ymin, xmax, ymax] in [0,1]."""
    x = rs.rand(n, 3, size, size).astype(np.float32) * 0.2
    labels = np.zeros((n, 1, 5), np.float32)
    for i in range(n):
        wide = rs.rand() < 0.5
        w = rs.uniform(0.4, 0.7)
        h = w * rs.uniform(0.35, 0.55)
        if not wide:
            w, h = h, w
        cx, cy = rs.uniform(w / 2, 1 - w / 2), rs.uniform(h / 2,
                                                          1 - h / 2)
        x0, y0 = cx - w / 2, cy - h / 2
        x1, y1 = cx + w / 2, cy + h / 2
        xi = [int(v * size) for v in (x0, y0, x1, y1)]
        x[i, :, xi[1]:xi[3], xi[0]:xi[2]] += 1.0
        labels[i, 0] = [0.0 if wide else 1.0, x0, y0, x1, y1]
    return x, labels


def build_net(mx):
    """Tiny SSD: shared conv trunk, two scales of heads."""
    net = mx.gluon.nn.HybridSequential(prefix="trunk_")
    with net.name_scope():
        for ch in (16, 32):
            net.add(mx.gluon.nn.Conv2D(ch, 3, padding=1),
                    mx.gluon.nn.Activation("relu"),
                    mx.gluon.nn.MaxPool2D(2))
        net.add(mx.gluon.nn.Conv2D(32, 3, padding=1),
                mx.gluon.nn.Activation("relu"))
    down = mx.gluon.nn.HybridSequential(prefix="down_")
    with down.name_scope():
        down.add(mx.gluon.nn.MaxPool2D(2),
                 mx.gluon.nn.Conv2D(32, 3, padding=1),
                 mx.gluon.nn.Activation("relu"))
    heads = []
    for scale in range(2):
        # anchors per pixel = len(sizes) + len(ratios) - 1 = 4
        cls = mx.gluon.nn.Conv2D((NUM_CLASSES + 1) * ANCHORS_PER_PX,
                                 3, padding=1, prefix=f"cls{scale}_")
        loc = mx.gluon.nn.Conv2D(4 * ANCHORS_PER_PX, 3, padding=1,
                                 prefix=f"loc{scale}_")
        heads.append((cls, loc))
    return net, down, heads


SIZES = [(0.3, 0.45), (0.6, 0.8)]
RATIOS = [(1.0, 2.0, 0.5)] * 2
ANCHORS_PER_PX = len(SIZES[0]) + len(RATIOS[0]) - 1


def forward(mx, nd, net, down, heads, xb):
    f1 = net(xb)
    f2 = down(f1)
    anchors, cls_preds, loc_preds = [], [], []
    for (clsh, loch), feat, sizes, ratios in zip(
            heads, (f1, f2), SIZES, RATIOS):
        anchors.append(nd.contrib.MultiBoxPrior(
            feat, sizes=sizes, ratios=ratios))
        c = clsh(feat)  # (B, K*(C+1), H, W)
        b = c.shape[0]
        c = nd.transpose(c, axes=(0, 2, 3, 1)).reshape(
            (b, -1, NUM_CLASSES + 1))
        cls_preds.append(c)
        l = nd.transpose(loch(feat), axes=(0, 2, 3, 1)).reshape((b, -1))
        loc_preds.append(l)
    anchor = nd.concat(*anchors, dim=1)
    cls_pred = nd.concat(*cls_preds, dim=1)   # (B, A, C+1)
    loc_pred = nd.concat(*loc_preds, dim=1)   # (B, 4A)
    return anchor, cls_pred, loc_pred


def evaluate_map(mx, nd, net, down, heads, x, labels, iou_thresh=0.5):
    """Single-point AP: detections matched to GT at IoU>=0.5."""
    tp, fp, npos = 0, 0, len(labels)
    xb = nd.array(x)
    anchor, cls_pred, loc_pred = forward(mx, nd, net, down, heads, xb)
    cls_prob = nd.transpose(nd.softmax(cls_pred, axis=-1), axes=(0, 2, 1))
    dets = nd.contrib.MultiBoxDetection(
        cls_prob, loc_pred, anchor, threshold=0.3,
        nms_threshold=0.45).asnumpy()
    for i in range(len(labels)):
        gt = labels[i, 0]
        det = dets[i]
        det = det[det[:, 0] >= 0]
        if not len(det):
            continue
        best = det[np.argmax(det[:, 1])]
        # IoU with the single GT box
        ix0 = max(best[2], gt[1]); iy0 = max(best[3], gt[2])
        ix1 = min(best[4], gt[3]); iy1 = min(best[5], gt[4])
        inter = max(ix1 - ix0, 0) * max(iy1 - iy0, 0)
        a1 = (best[4] - best[2]) * (best[5] - best[3])
        a2 = (gt[3] - gt[1]) * (gt[4] - gt[2])
        iou = inter / max(a1 + a2 - inter, 1e-9)
        if iou >= iou_thresh and int(best[0]) == int(gt[0]):
            tp += 1
        else:
            fp += 1
    return tp / max(npos, 1)


def anchor_scale_check(mx, nd):
    """MultiBoxTarget + MultiBoxDetection once at SSD300 scale: the
    reference's 8732-anchor layout (ref: example/ssd/symbol/
    symbol_builder.py feature maps 38/19/10/5/3/1)."""
    fmaps = [(38, 4), (19, 6), (10, 6), (5, 6), (3, 4), (1, 4)]
    anchors = []
    for hw, k in fmaps:
        feat = nd.zeros((1, 1, hw, hw))
        sizes = (0.2, 0.27)
        ratios = (1.0, 2.0, 0.5, 3.0, 1.0 / 3)[:k - 1]
        anchors.append(nd.contrib.MultiBoxPrior(
            feat, sizes=sizes, ratios=ratios))
    anchor = nd.concat(*anchors, dim=1)
    A = anchor.shape[1]
    assert A == 8732, A
    rs = np.random.RandomState(0)
    B = 2
    label = nd.array(rs.rand(B, 3, 5).astype(np.float32))
    cls_pred = nd.array(rs.rand(B, NUM_CLASSES + 1, A)
                        .astype(np.float32))
    t0 = time.perf_counter()
    loc_t, loc_m, cls_t = nd.contrib.MultiBoxTarget(
        anchor, label, cls_pred, negative_mining_ratio=3.0)
    dets = nd.contrib.MultiBoxDetection(
        nd.softmax(nd.array(rs.rand(B, NUM_CLASSES + 1, A)
                            .astype(np.float32)), axis=1),
        nd.array(rs.rand(B, 4 * A).astype(np.float32) * 0.1),
        anchor)
    n_det = int((dets.asnumpy()[:, :, 0] >= 0).sum())
    dt = time.perf_counter() - t0
    assert loc_t.shape == (B, 4 * A) and cls_t.shape == (B, A)
    print(f"anchor-scale-check: A={A} target+NMS {dt*1e3:.0f} ms, "
          f"{n_det} detections", flush=True)


def main(argv=None):
    from incubator_mxnet_tpu.utils.platform import maybe_force_cpu
    maybe_force_cpu()
    args = parse_args(argv)
    if args.quick:
        args.num_iters = 100
        args.num_images = 64
        args.batch_size = 16
        args.image_size = 48
        args.lr = 0.1

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, nd

    if args.anchor_scale_check:
        anchor_scale_check(mx, nd)

    rs = np.random.RandomState(0)
    mx.random.seed(0)
    x, labels = make_dataset(rs, args.num_images, args.image_size)
    net, down, heads = build_net(mx)
    for blk in [net, down] + [h for pair in heads for h in pair]:
        blk.initialize(mx.initializer.Xavier())
        blk.hybridize()  # shape/dtype-keyed jit per block

    params = {}
    for blk in [net, down] + [h for pair in heads for h in pair]:
        params.update(blk.collect_params())
    # settle deferred shapes
    forward(mx, nd, net, down, heads, nd.array(x[:2]))
    trainer = mx.gluon.Trainer(params, "sgd",
                               dict(learning_rate=args.lr,
                                    momentum=0.9, wd=1e-4))
    cls_loss = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    B = args.batch_size
    t0 = time.perf_counter()
    first_loss = None
    for it in range(args.num_iters):
        sel = rs.randint(0, args.num_images, B)
        xb, lb = nd.array(x[sel]), nd.array(labels[sel])
        with autograd.record():
            anchor, cls_pred, loc_pred = forward(mx, nd, net, down,
                                                 heads, xb)
            cp_t = nd.transpose(cls_pred, axes=(0, 2, 1))
            loc_t, loc_m, cls_t = nd.contrib.MultiBoxTarget(
                anchor, lb, cp_t, negative_mining_ratio=3.0)
            # cls: ignore anchors marked -1 (the reference trains
            # SoftmaxOutput with use_ignore; here we mask explicitly),
            # normalize by valid count; loc: normalize by positives
            valid = cls_t >= 0
            logp = nd.log_softmax(cls_pred, axis=-1)
            lc = -nd.pick(logp, nd.maximum(cls_t, 0), axis=-1) * valid
            n_valid = nd.maximum(valid.sum(), nd.array([1.0]))
            n_pos = nd.maximum(loc_m.sum() / 4.0, nd.array([1.0]))
            ll = nd.smooth_l1((loc_pred - loc_t) * loc_m, scalar=1.0)
            loss = lc.sum() / n_valid + ll.sum() / n_pos
        loss.backward()
        trainer.step(B)
        if it == 0:
            first_loss = float(loss.asnumpy())
        if it % 25 == 0:
            print(f"iter {it}: loss={float(loss.asnumpy()):.4f}",
                  flush=True)
    final_loss = float(loss.asnumpy())
    ap = evaluate_map(mx, nd, net, down, heads,
                      x[:args.num_images], labels[:args.num_images])
    summary = {"first_loss": first_loss, "final_loss": final_loss,
               "mAP": ap,
               "train_s": round(time.perf_counter() - t0, 1)}
    print(json.dumps(summary), flush=True)
    if args.quick:
        assert final_loss < first_loss * 0.7, summary
        assert ap > 0.5, summary
    return summary


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
