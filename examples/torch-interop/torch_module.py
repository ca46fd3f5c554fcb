#!/usr/bin/env python
"""Host a ``torch.nn.Module`` inside this framework's graph — the role
of the reference's torch plugin (``plugin/torch``: ``TorchModule``
wrapped a Torch module so its parameters became learnable mxnet
arguments and its forward/backward ran under mxnet's executor).

``TorchModuleProp`` does the same through the CustomOp foreign-function
interface: the torch module's named parameters surface as ordinary
symbol arguments (initialized and UPDATED by this framework's
optimizer); forward runs the module under ``torch.no_grad`` on the
host, and backward REPLAYS it under autograd to collect the input and
parameter gradients.  Like the reference plugin — whose Torch
tensors lived wherever Torch put them — the bridged compute runs where
torch runs (CPU in this image); the surrounding graph stays on the
accelerator.  Use it to borrow a torch layer you haven't ported yet,
not on the hot path.

Run: python examples/torch-interop/torch_module.py
"""
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np

import mxnet_tpu as mx

import torch


class _TorchBridge(mx.operator.CustomOp):
    """Runs one torch module; parameters arrive as extra mxnet inputs."""

    def __init__(self, module, param_names):
        self.module = module
        self.param_names = param_names

    @staticmethod
    def _tensor(arr):
        # copy: asnumpy() views are read-only and from_numpy on them
        # warns (and is one refactor from real undefined behavior)
        return torch.from_numpy(np.array(arr.asnumpy(), copy=True))

    def _load_params(self, in_data):
        state = dict(self.module.named_parameters())
        with torch.no_grad():
            for name, arr in zip(self.param_names, in_data[1:]):
                state[name].copy_(self._tensor(arr))

    def forward(self, is_train, req, in_data, out_data, aux):
        # honor the mode: dropout/BN inside the hosted module must see
        # the same train/eval split the surrounding graph does
        self.module.train(bool(is_train))
        self._load_params(in_data)
        with torch.no_grad():
            y = self.module(self._tensor(in_data[0]))
        self.assign(out_data[0], req[0], mx.nd.array(y.numpy()))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.module.train(True)            # backward implies training
        self._load_params(in_data)
        x = self._tensor(in_data[0]).requires_grad_(True)
        self.module.zero_grad(set_to_none=True)
        y = self.module(x)
        y.backward(self._tensor(out_grad[0]))
        grads = [x.grad] + [dict(self.module.named_parameters())[n].grad
                            for n in self.param_names]
        for slot, g in enumerate(grads):
            gval = np.zeros(in_data[slot].shape, "f") if g is None \
                else g.detach().numpy()
            self.assign(in_grad[slot], req[slot], mx.nd.array(gval))


@mx.operator.register("torch_module")
class TorchModuleProp(mx.operator.CustomOpProp):
    """op_type='torch_module': ``factory`` names a zero-arg callable in
    ``TORCH_FACTORIES`` producing the torch module to host."""

    def __init__(self, factory):
        super().__init__(need_top_grad=True)
        self.factory = str(factory)
        self.module = TORCH_FACTORIES[self.factory]()
        self.param_names = [n for n, _ in self.module.named_parameters()]
        self._out_shape_cache = {}

    def list_arguments(self):
        # mangled with the factory so two bridges don't collide
        return ["data"] + ["%s_%s" % (self.factory, n.replace(".", "_"))
                           for n in self.param_names]

    def infer_shape(self, in_shape):
        params = dict(self.module.named_parameters())
        shapes = [in_shape[0]] + [tuple(params[n].shape)
                                  for n in self.param_names]
        key = tuple(in_shape[0])
        if key not in self._out_shape_cache:
            # one probe forward per input shape — infer_shape is called
            # on every host callback, so this must not re-run the module
            with torch.no_grad():
                self._out_shape_cache[key] = tuple(
                    self.module(torch.zeros(*key)).shape)
        return shapes, [self._out_shape_cache[key]], []

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return _TorchBridge(self.module, self.param_names)


INIT_SNAPSHOT = {}

TORCH_FACTORIES = {
    "mlp_block": lambda: torch.nn.Sequential(
        torch.nn.Linear(16, 32), torch.nn.GELU(),
        torch.nn.Linear(32, 8)),
}


def main():
    logging.basicConfig(level=logging.INFO)
    torch.manual_seed(0)
    rng = np.random.RandomState(0)
    X = rng.normal(0, 1, (512, 16)).astype("f")
    Y = (X @ rng.normal(0, 1, (16, 4))).argmax(1).astype("f")

    # torch block sandwiched between native layers; its Linear weights
    # are plain symbol arguments trained by THIS framework's SGD
    data = mx.sym.Variable("data")
    h = mx.sym.Custom(data, op_type="torch_module", factory="mlp_block",
                      name="torchblk")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=4, name="fc_out")
    net = mx.sym.SoftmaxOutput(h, name="softmax")

    args = net.list_arguments()
    assert any("mlp_block" in a for a in args), args
    logging.info("torch parameters as symbol arguments: %s",
                 [a for a in args if "mlp_block" in a])

    it = mx.io.NDArrayIter(X, Y, batch_size=64, shuffle=True,
                           label_name="softmax_label")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    arg0, _ = mod.get_params()
    INIT_SNAPSHOT.update({k: v.asnumpy().copy() for k, v in arg0.items()
                          if "mlp_block" in k})
    mod.fit(it, num_epoch=12, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2},
            initializer=mx.init.Xavier())
    it.reset()
    acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
    logging.info("accuracy with a torch block in the graph: %.3f", acc)

    # the torch parameters genuinely trained (moved off their init)
    arg_params, _ = mod.get_params()
    torch_keys = sorted(k for k in arg_params if "mlp_block" in k)
    moved = max(float(np.abs(arg_params[k].asnumpy()
                             - INIT_SNAPSHOT[k]).max())
                for k in torch_keys)
    logging.info("max |w - w_init| over torch params: %.4f", moved)
    assert moved > 1e-3, "torch parameters never received gradients"
    return 0 if acc > 0.9 else 1


if __name__ == "__main__":
    sys.exit(main())
