#!/usr/bin/env python
"""SVM output layer, toy-sized (reference ``example/svm_mnist/``): an
MLP trained with the max-margin ``SVMOutput`` loss (hinge / squared
hinge via ``regularization_coefficient`` and ``use_linear``) instead of
softmax cross-entropy — the only example family that trains the SVM
loss's subgradient path end-to-end.

Run: python examples/svm_mnist/svm_toy.py
"""
import argparse
import logging
import os
import sys

# tiny-batch toy: latency-bound, not compute-bound — it selects the CPU
# backend unless MXTPU_TOY_BACKEND says otherwise (same preamble as
# examples/warpctc)
if os.environ.get("MXTPU_TOY_BACKEND", "cpu") == "cpu":
    import jax
    jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np

import mxnet_tpu as mx


def svm_mlp(nclass=4, use_linear=False):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=48, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=nclass, name="fc2")
    return mx.sym.SVMOutput(net, name="svm",
                            regularization_coefficient=1.0,
                            use_linear=use_linear)


def make_data(rng, n=400, d=20, k=4):
    x = rng.randn(n, d).astype("f")
    w = rng.randn(d, k).astype("f")
    y = np.argmax(x @ w, axis=1).astype("f")
    return x, y


def main(epochs=10, batch=32, use_linear=False):
    logging.basicConfig(level=logging.INFO)
    rng = np.random.RandomState(0)
    x, y = make_data(rng)
    it = mx.io.NDArrayIter(x, y, batch_size=batch, shuffle=True,
                           label_name="svm_label")
    mod = mx.mod.Module(svm_mlp(use_linear=use_linear), context=mx.cpu(),
                        label_names=("svm_label",))
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.02, "momentum": 0.9},
            initializer=mx.init.Xavier())
    it.reset()
    correct = total = 0
    for b in it:
        mod.forward(b, is_train=False)
        pred = mod.get_outputs()[0].asnumpy().argmax(1)
        lab = b.label[0].asnumpy()
        correct += (pred == lab).sum()
        total += len(lab)
    return correct / total


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--use-linear", action="store_true",
                    help="L1 hinge instead of squared hinge")
    args = ap.parse_args()
    acc = main(epochs=args.epochs, use_linear=args.use_linear)
    assert acc > 0.9, acc
    print("svm toy OK: acc %.3f" % acc)
