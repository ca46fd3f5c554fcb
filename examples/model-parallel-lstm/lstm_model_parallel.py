#!/usr/bin/env python
"""Model-parallel LSTM (reference ``example/model-parallel-lstm/lstm.py``).

The reference places each LSTM layer on a different GPU via ``group2ctx``
and lets the executor insert ``_CrossDeviceCopy`` at the boundaries.  The
TPU-native formulation shards the big parameter matrices over the
``model`` axis of a device mesh instead: XLA SPMD partitions the matmuls
and inserts the ICI collectives, which both overlaps compute with
communication and avoids whole-activation copies between devices.

Runs on real chips, or on a virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python lstm_model_parallel.py --num-devices 8
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np


def main():
    parser = argparse.ArgumentParser(
        description="model-parallel LSTM LM",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("--num-devices", type=int, default=0)
    parser.add_argument("--num-hidden", type=int, default=256)
    parser.add_argument("--num-embed", type=int, default=128)
    parser.add_argument("--num-layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=32)
    parser.add_argument("--vocab", type=int, default=1024)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--num-batches", type=int, default=20)
    parser.add_argument("--lr", type=float, default=0.005)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    if args.num_devices and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count="
                                   + str(args.num_devices))
    import jax
    from jax.sharding import PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import make_mesh, Trainer

    devices = jax.devices()
    n = args.num_devices or len(devices)
    if len(devices) < n:
        devices = jax.devices("cpu")[:n]
    mesh = make_mesh({"model": n}, devices)

    sym = models.lstm_lm.get_symbol(seq_len=args.seq_len,
                                    num_classes=args.vocab,
                                    num_embed=args.num_embed,
                                    num_hidden=args.num_hidden,
                                    num_layers=args.num_layers)

    # shard every gate matrix / embedding / classifier over 'model';
    # XLA partitions each matmul and all-gathers only the small
    # per-timestep activations over ICI
    specs = {}
    for name in sym.list_arguments():
        if name.endswith("_weight") and "embed" not in name:
            specs[name] = P("model", None)
        elif name.endswith("_bias"):
            specs[name] = P("model")
        elif "embed" in name and name.endswith("weight"):
            specs[name] = P(None, "model")

    trainer = Trainer(sym, mx.optimizer.SGD(learning_rate=args.lr),
                      mesh=mesh, param_specs=specs)
    trainer.bind(
        data_shapes={"data": (args.batch_size, args.seq_len)},
        label_shapes={"softmax_label": (args.batch_size, args.seq_len)})
    trainer.init_params(mx.init.Xavier())

    rng = np.random.RandomState(0)
    x = rng.randint(0, args.vocab,
                    (args.batch_size, args.seq_len)).astype(np.float32)
    y = np.roll(x, -1, axis=1)
    for i in range(args.num_batches):
        outs = trainer.step({"data": x, "softmax_label": y})
        if i % 5 == 0:
            probs = np.asarray(outs[0].data)
            nll = -np.log(np.maximum(
                probs.reshape(-1, args.vocab)[
                    np.arange(y.size), y.reshape(-1).astype(int)], 1e-8))
            logging.info("batch %d  perplexity %.2f", i,
                         float(np.exp(nll.mean())))
    logging.info("done: %d-way model-parallel LSTM over mesh %s",
                 n, dict(zip(mesh.axis_names, mesh.devices.shape)))

    group2ctx_demo(args)


def group2ctx_demo(args):
    """The reference's own formulation: each LSTM layer in a ctx group,
    placed on a distinct device via ``group2ctx`` (reference
    ``example/model-parallel-lstm/lstm.py:48-99``).  Kept alongside the
    mesh formulation above for API parity; the executor pins each
    group's nodes with jax.device_put inside the jitted program."""
    import mxnet_tpu as mx
    from mxnet_tpu import rnn as mxrnn

    data = mx.sym.Variable("data")
    with mx.AttrScope(ctx_group="embed"):
        net = mx.sym.Embedding(data, input_dim=args.vocab,
                               output_dim=args.num_embed, name="embed")
    stack_states = []
    for layer in range(args.num_layers):
        with mx.AttrScope(ctx_group="layer%d" % layer):
            cell = mxrnn.LSTMCell(args.num_hidden, prefix="l%d_" % layer)
            outputs, states = cell.unroll(args.seq_len, inputs=net,
                                          layout="NTC",
                                          merge_outputs=True)
            net = outputs
            stack_states.extend(states)
    with mx.AttrScope(ctx_group="decode"):
        net = mx.sym.Reshape(net, shape=(-1, args.num_hidden))
        net = mx.sym.FullyConnected(net, num_hidden=args.vocab, name="cls")
        net = mx.sym.SoftmaxOutput(net, name="softmax")

    import jax
    devs = jax.devices()
    if len(devs) < 2:
        try:
            devs = jax.devices("cpu")   # virtual CPU mesh fallback
        except RuntimeError:
            pass
    groups = ["embed"] + ["layer%d" % i for i in range(args.num_layers)] + \
        ["decode"]
    kind = mx.tpu if devs[0].platform == "tpu" else mx.cpu
    group2ctx = {g: kind(i % len(devs)) for i, g in enumerate(groups)}
    ex = net.simple_bind(mx.current_context(),
                         data=(args.batch_size, args.seq_len),
                         softmax_label=(args.batch_size * args.seq_len,),
                         group2ctx=group2ctx)
    placed = {str(d) for d in ex._prog.placement.values()}
    logging.info("group2ctx demo: %d groups placed on %d device(s)",
                 len(groups), len(placed))
    ex.forward(is_train=False)
    logging.info("group2ctx forward ok: output %s",
                 ex.outputs[0].shape)


if __name__ == "__main__":
    main()
