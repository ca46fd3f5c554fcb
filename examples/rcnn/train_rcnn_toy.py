#!/usr/bin/env python
"""Toy Faster R-CNN, end-to-end (reference ``example/rcnn`` —
``train_end2end.py`` + ``symbol_vgg.py`` — at test scale): a conv
backbone feeds an RPN whose outputs run through the native ``Proposal``
op, the ``toy_proposal_target`` CustomOp assigns per-roi targets, and
``ROIPooling`` + fc heads classify and regress each proposal — all in
ONE symbol trained jointly on synthetic bright-square images.

Exercises the full detection-op chain the reference's rcnn example
exists to integration-test: Proposal (anchors/decode/NMS), CustomOp
(python op with 4 outputs inside the graph), ROIPooling, smooth_l1,
SoftmaxOutput with ignore labels.

Run: python examples/rcnn/train_rcnn_toy.py  (exit 0 = detector learned)
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np

# the in-graph CustomOp (proposal_target) lowers to a host callback
# (SURVEY §7 hard part 2: python ops force host round-trips)
import mxnet_tpu as mx

import proposal_target  # noqa: F401  (registers toy_proposal_target)
from proposal_target import box_iou, encode_boxes

IMG = 64
STRIDE = 4
SCALES = (3.0, 6.0)          # anchor sides 12 / 24 px at stride 4
RATIOS = (1.0,)
K = len(SCALES) * len(RATIOS)
FEAT = IMG // STRIDE
POST_NMS = 8                  # rois per image


def gen_anchors():
    """Anchor enumeration identical to the Proposal op
    (``mxnet_tpu/op/contrib.py`` _proposal): base boxes around a
    stride^2 cell, shifted over the feature grid; order (h, w, k)."""
    base = []
    cx = (STRIDE - 1) / 2.0
    for r in RATIOS:
        size = STRIDE * STRIDE / r
        ws = np.round(np.sqrt(size))
        hs = np.round(ws * r)
        for s in SCALES:
            w2, h2 = ws * s, hs * s
            base.append([cx - (w2 - 1) / 2, cx - (h2 - 1) / 2,
                         cx + (w2 - 1) / 2, cx + (h2 - 1) / 2])
    base = np.array(base, np.float32)                      # (K,4)
    out = np.zeros((FEAT, FEAT, K, 4), np.float32)
    for h in range(FEAT):
        for w in range(FEAT):
            shift = np.array([w * STRIDE, h * STRIDE] * 2, np.float32)
            out[h, w] = base + shift
    return out.reshape(-1, 4)                              # (H*W*K,4)


ANCHORS = gen_anchors()


def build_symbol(num_classes=2):
    data = mx.sym.Variable("data")
    gt_boxes = mx.sym.Variable("gt_boxes")
    im_info = mx.sym.Variable("im_info")
    rpn_label = mx.sym.Variable("rpn_label")
    rpn_bbox_target = mx.sym.Variable("rpn_bbox_target")
    rpn_bbox_weight = mx.sym.Variable("rpn_bbox_weight")

    body = data
    for i, nf in enumerate((16, 32)):
        body = mx.sym.Convolution(body, kernel=(3, 3), pad=(1, 1),
                                  num_filter=nf, name="conv%d" % i)
        body = mx.sym.Activation(body, act_type="relu")
        body = mx.sym.Pooling(body, kernel=(2, 2), stride=(2, 2),
                              pool_type="max")

    # --- RPN (reference symbol_vgg.py get_vgg_rpn)
    rpn_conv = mx.sym.Convolution(body, kernel=(3, 3), pad=(1, 1),
                                  num_filter=32, name="rpn_conv_3x3")
    rpn_relu = mx.sym.Activation(rpn_conv, act_type="relu")
    rpn_cls_score = mx.sym.Convolution(rpn_relu, kernel=(1, 1),
                                       num_filter=2 * K,
                                       name="rpn_cls_score")
    rpn_bbox_pred = mx.sym.Convolution(rpn_relu, kernel=(1, 1),
                                       num_filter=4 * K,
                                       name="rpn_bbox_pred")

    # cls rows ordered (b, h, w, k): channel layout is (bg_k..., fg_k...)
    score_2k = mx.sym.Reshape(rpn_cls_score,
                              shape=(0, 2, K, FEAT, FEAT))
    rows = mx.sym.transpose(score_2k, axes=(0, 3, 4, 2, 1))
    rows = mx.sym.Reshape(rows, shape=(-1, 2))
    rpn_cls_prob = mx.sym.SoftmaxOutput(
        rows, mx.sym.Reshape(rpn_label, shape=(-1,)),
        ignore_label=-1, use_ignore=True, normalization="valid",
        name="rpn_cls_prob")

    rpn_bbox_loss = mx.sym.smooth_l1(
        (rpn_bbox_pred - rpn_bbox_target) * rpn_bbox_weight, scalar=3.0)
    rpn_bbox_loss = mx.sym.MakeLoss(
        mx.sym.sum(rpn_bbox_loss) /
        (mx.sym.sum(rpn_bbox_weight) + 1e-6), name="rpn_bbox_loss")

    # --- proposals (native Proposal op; rois are not differentiated,
    # matching the reference's zero-grad proposal op)
    prob_2k = mx.sym.Reshape(
        mx.sym.softmax(score_2k, axis=1), shape=(0, 2 * K, FEAT, FEAT))
    rois = mx.sym.Proposal(
        mx.sym.BlockGrad(prob_2k), mx.sym.BlockGrad(rpn_bbox_pred),
        im_info, scales=SCALES, ratios=RATIOS, feature_stride=STRIDE,
        rpn_pre_nms_top_n=64, rpn_post_nms_top_n=POST_NMS,
        threshold=0.7, rpn_min_size=4, name="proposal")

    # --- per-roi targets (CustomOp, reference proposal_target.py)
    tgt = mx.sym.Custom(rois, gt_boxes, op_type="toy_proposal_target",
                        num_classes=str(num_classes), name="ptarget")
    rois_out, label, bbox_target, bbox_weight = (tgt[0], tgt[1], tgt[2],
                                                 tgt[3])

    # --- Fast R-CNN head (reference get_vgg_rcnn)
    pool = mx.sym.ROIPooling(body, rois_out, pooled_size=(4, 4),
                             spatial_scale=1.0 / STRIDE, name="roi_pool")
    flat = mx.sym.Flatten(pool)
    fc = mx.sym.FullyConnected(flat, num_hidden=64, name="fc6")
    fc = mx.sym.Activation(fc, act_type="relu")
    cls_score = mx.sym.FullyConnected(fc, num_hidden=num_classes,
                                      name="cls_score")
    cls_prob = mx.sym.SoftmaxOutput(cls_score, label, name="cls_prob")
    bbox_pred = mx.sym.FullyConnected(fc, num_hidden=4 * num_classes,
                                      name="bbox_pred")
    bbox_loss = mx.sym.smooth_l1((bbox_pred - bbox_target) * bbox_weight,
                                 scalar=1.0)
    bbox_loss = mx.sym.MakeLoss(
        mx.sym.sum(bbox_loss) / (mx.sym.sum(bbox_weight) + 1e-6),
        name="bbox_loss")

    return mx.sym.Group([rpn_cls_prob, rpn_bbox_loss, cls_prob, bbox_loss,
                         mx.sym.BlockGrad(rois_out, name="rois_out"),
                         mx.sym.BlockGrad(bbox_pred, name="bbox_out")])


def make_batch(rng, batch):
    """Bright squares on noise; gt = [x1, y1, x2, y2, cls=1] pixels."""
    imgs = rng.normal(0, 0.1, (batch, 3, IMG, IMG)).astype("f")
    gt = np.zeros((batch, 1, 5), "f")
    for b in range(batch):
        w = rng.randint(12, 28)
        x0 = rng.randint(0, IMG - w)
        y0 = rng.randint(0, IMG - w)
        imgs[b, :, y0:y0 + w, x0:x0 + w] += 1.0
        gt[b, 0] = (x0, y0, x0 + w - 1, y0 + w - 1, 1)
    return imgs, gt


def rpn_targets(gt):
    """Anchor-wise RPN targets, host-side (the reference's AnchorLoader):
    label (B, H*W*K) in {1 fg, 0 bg, -1 ignore}; bbox target/weight in
    the (4K, H, W) conv layout."""
    B = gt.shape[0]
    label = np.full((B, FEAT * FEAT * K), -1.0, "f")
    target = np.zeros((B, 4 * K, FEAT, FEAT), "f")
    weight = np.zeros((B, 4 * K, FEAT, FEAT), "f")
    for b in range(B):
        iou = box_iou(ANCHORS, gt[b, 0, :4])
        fg = iou >= 0.5
        if not fg.any():
            fg = iou >= iou.max() - 1e-6
        label[b, fg] = 1.0
        label[b, iou < 0.3] = 0.0
        deltas = encode_boxes(ANCHORS[fg], gt[b, 0, :4])
        idx = np.where(fg)[0]
        h, w, k = (idx // (FEAT * K), (idx // K) % FEAT, idx % K)
        for j in range(len(idx)):
            target[b, 4 * k[j]:4 * k[j] + 4, h[j], w[j]] = deltas[j]
            weight[b, 4 * k[j]:4 * k[j] + 4, h[j], w[j]] = 1.0
    return label, target, weight


def main():
    parser = argparse.ArgumentParser(description="toy Faster R-CNN")
    parser.add_argument("--batch-size", type=int, default=4)
    parser.add_argument("--num-batches", type=int, default=60)
    parser.add_argument("--lr", type=float, default=0.005)
    parser.add_argument("--min-recall", type=float, default=0.5)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    rng = np.random.RandomState(0)
    B = args.batch_size

    net = build_symbol()
    data_names = ("data", "im_info", "gt_boxes", "rpn_label",
                  "rpn_bbox_target", "rpn_bbox_weight")
    mod = mx.mod.Module(net, data_names=data_names, label_names=None)
    shapes = [("data", (B, 3, IMG, IMG)), ("im_info", (B, 3)),
              ("gt_boxes", (B, 1, 5)),
              ("rpn_label", (B, FEAT * FEAT * K)),
              ("rpn_bbox_target", (B, 4 * K, FEAT, FEAT)),
              ("rpn_bbox_weight", (B, 4 * K, FEAT, FEAT))]
    mod.bind(data_shapes=shapes)
    mod.init_params(mx.init.Xavier(magnitude=2.0))
    # decay keeps the jointly-trained RPN from diverging late in the run
    sched = mx.lr_scheduler.FactorScheduler(step=30, factor=0.5)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "momentum": 0.9, "wd": 1e-4,
                                         "rescale_grad": 1.0,
                                         "lr_scheduler": sched})
    im_info = np.tile(np.array([IMG, IMG, 1.0], "f"), (B, 1))

    def feed(imgs, gt):
        lab, tgt, wgt = rpn_targets(gt)
        return mx.io.DataBatch(data=[mx.nd.array(x) for x in
                                     (imgs, im_info, gt, lab, tgt, wgt)],
                               label=[])

    for i in range(args.num_batches):
        imgs, gt = make_batch(rng, B)
        batch = feed(imgs, gt)
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
        if i % 20 == 0:
            outs = mod.get_outputs()
            logging.info("batch %d rpn-bbox %.4f rcnn-bbox %.4f", i,
                         float(outs[1].asnumpy().mean()),
                         float(outs[3].asnumpy().mean()))

    # detection: best-scoring roi per image must overlap the object
    imgs, gt = make_batch(rng, B)
    mod.forward(feed(imgs, gt), is_train=False)
    outs = mod.get_outputs()
    cls_prob = outs[2].asnumpy().reshape(B, POST_NMS, 2)
    rois = outs[4].asnumpy().reshape(B, POST_NMS, 5)
    hits = 0
    for b in range(B):
        best = int(np.argmax(cls_prob[b, :, 1]))
        if box_iou(rois[b, best:best + 1, 1:5], gt[b, 0, :4])[0] > 0.3:
            hits += 1
    recall = hits / B
    logging.info("rcnn recall@0.3IoU: %d/%d", hits, B)
    return 0 if recall >= args.min_recall else 1


if __name__ == "__main__":
    sys.exit(main())
