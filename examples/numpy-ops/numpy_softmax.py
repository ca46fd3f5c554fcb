#!/usr/bin/env python
"""Softmax written as a legacy NumpyOp (reference
``example/numpy-ops/numpy_softmax.py``): the pre-CustomOp foreign-
function API — forward/backward are plain numpy mutating ``out_data``
in place — spliced into a Module-trained MNIST-style MLP.

Run: python examples/numpy-ops/numpy_softmax.py
"""
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np

import mxnet_tpu as mx


class NumpySoftmax(mx.operator.NumpyOp):
    """The reference example verbatim in spirit: softmax + CE gradient."""

    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def infer_shape(self, in_shape):
        return [in_shape[0], (in_shape[0][0],)], [in_shape[0]]

    def forward(self, in_data, out_data):
        x, y = in_data[0], out_data[0]
        y[:] = np.exp(x - x.max(axis=1, keepdims=True))
        y /= y.sum(axis=1, keepdims=True)

    def backward(self, out_grad, in_data, out_data, in_grad):
        label, y, dx = in_data[1], out_data[0], in_grad[0]
        dx[:] = y.copy()
        dx[np.arange(label.shape[0]), label.astype(np.int32)] -= 1.0


def main():
    logging.basicConfig(level=logging.INFO)
    rng = np.random.RandomState(0)
    X = rng.normal(0, 1, (512, 16)).astype("f")
    Y = (X @ rng.normal(0, 1, (16, 4))).argmax(1).astype("f")

    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
    net = NumpySoftmax()(h, name="softmax")

    label_name = [n for n in net.list_arguments()
                  if n.endswith("label")][0]
    it = mx.io.NDArrayIter(X, Y, batch_size=32, shuffle=True,
                           label_name=label_name)
    mod = mx.mod.Module(net, label_names=(label_name,))
    mod.fit(it, num_epoch=10, optimizer="sgd",
            optimizer_params={"learning_rate": 0.3},
            initializer=mx.init.Xavier())
    it.reset()
    acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
    logging.info("train accuracy with NumpyOp softmax: %.3f", acc)
    return 0 if acc > 0.9 else 1


if __name__ == "__main__":
    sys.exit(main())
