#!/usr/bin/env python
"""CTC sequence recognition, toy-sized (reference
``example/warpctc/toy_ctc.py`` — the warpctc *plugin*'s example; here
``WarpCTC`` is an in-tree XLA op, no linked CUDA library): an LSTM
reads a frame sequence encoding a digit string, and CTC training
aligns the unsegmented frames to the label sequence — no per-frame
labels, exactly the speech/OCR training regime.  Greedy
collapse-and-drop-blank decoding must recover the digit strings.

Run: python examples/warpctc/toy_ctc.py
"""
import argparse
import logging
import os
import sys

# Tiny-batch CTC training is latency-bound, not compute-bound: this
# example selects the CPU backend unless MXTPU_TOY_BACKEND says
# otherwise (the op itself lowers for the TPU, see tests/test_ctc.py
# and the WarpCTC docstring).
if os.environ.get("MXTPU_TOY_BACKEND", "cpu") == "cpu":
    import jax
    jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import rnn
from mxnet_tpu.op.ctc import ctc_greedy_decode

NUM_DIGITS = 3          # digits per sequence
FRAMES = 5              # frames per digit
SEQ = NUM_DIGITS * FRAMES
FEAT = 10               # one-hot digit features (with frame noise)
HIDDEN = 40
VOCAB = 11              # blank + digits 1..10 (digit d -> class d+1)


def ctc_symbol(seq_len=SEQ):
    data = mx.sym.Variable("data")                  # (B, T, FEAT)
    label = mx.sym.Variable("label")                # (B, NUM_DIGITS)
    cell = rnn.LSTMCell(HIDDEN, prefix="l0_")
    outputs, _ = cell.unroll(seq_len, inputs=data, layout="NTC",
                             merge_outputs=False)
    # TIME-major concat, the reference lstm.py layout: (T*B, H)
    hidden = mx.sym.Concat(*outputs, dim=0)
    pred = mx.sym.FullyConnected(hidden, num_hidden=VOCAB, name="cls")
    return mx.sym.WarpCTC(pred, label, label_length=NUM_DIGITS,
                          input_length=seq_len)


def make_data(rng, n):
    """Each sequence: NUM_DIGITS digits, each held for FRAMES frames of
    a noisy one-hot; labels are 1-based (0 is the CTC blank)."""
    x = np.zeros((n, SEQ, FEAT), "f")
    y = np.zeros((n, NUM_DIGITS), "f")
    for i in range(n):
        digits = rng.randint(0, 10, NUM_DIGITS)
        y[i] = digits + 1
        for j, d in enumerate(digits):
            x[i, j * FRAMES:(j + 1) * FRAMES, d] = 1.0
    x += rng.normal(0, 0.1, x.shape).astype("f")
    return x, y


class CTCSequenceAccuracy(mx.metric.EvalMetric):
    """Exact-sequence-match rate after greedy decoding (the reference
    toy_ctc's Accuracy)."""

    def __init__(self):
        super().__init__("ctc-seq-acc")

    def update(self, labels, preds):
        probs = preds[0].asnumpy()
        decoded = ctc_greedy_decode(probs, SEQ)
        lab = labels[0].asnumpy()
        for b, seq in enumerate(decoded):
            want = [int(v) for v in lab[b] if v != 0]
            self.sum_metric += int(seq == want)
            self.num_inst += 1


def sequence_accuracy(mod, it):
    it.reset()
    hit = total = 0
    for batch in it:
        mod.forward(batch, is_train=False)
        probs = mod.get_outputs()[0].asnumpy()
        decoded = ctc_greedy_decode(probs, SEQ)
        labels = batch.label[0].asnumpy()
        for b, seq in enumerate(decoded):
            want = [int(v) for v in labels[b] if v != 0]
            hit += int(seq == want)
            total += 1
    return hit / total


def main(epochs=35, batch=32, n=256):
    logging.basicConfig(level=logging.INFO)
    rng = np.random.RandomState(0)
    x, y = make_data(rng, n)
    it = mx.io.NDArrayIter(x, y, batch_size=batch, shuffle=True,
                           label_name="label")
    mod = mx.mod.Module(ctc_symbol(), context=mx.cpu(),
                        label_names=("label",))
    mod.fit(it, num_epoch=epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier(),
            eval_metric=CTCSequenceAccuracy())
    acc = sequence_accuracy(mod, it)
    logging.info("sequence accuracy: %.3f", acc)
    return acc


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=35)
    args = ap.parse_args()
    acc = main(epochs=args.epochs)
    assert acc > 0.9, acc
    print("warpctc toy OK: sequence acc %.3f" % acc)
