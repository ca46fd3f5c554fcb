#!/usr/bin/env python
"""Measure gradient-allreduce bandwidth over the device mesh.

The reference's ``tools/bandwidth/measure.py`` times KVStore push+pull of a
model's gradient arrays across GPUs and reports GB/s per device over PCIe
P2P (README numbers: 11.1 GB/s/GPU @ 2 GPUs, 4.4-4.6 @ 8).  The TPU
equivalent times one jitted ``psum`` of the same gradient payload over the
ICI mesh — the collective that replaces the whole KVStore push/pull round
trip in ``dist_sync_tpu``.

Algorithmic bandwidth uses the standard ring-allreduce byte count
``2*(n-1)/n * bytes`` per device.

Example::

    python tools/bandwidth/measure.py --network resnet-50 --num-devices 8
    python tools/bandwidth/measure.py --size-mb 258 --num-devices 8
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np


def grad_shapes(network, batch=32, image=224, num_classes=1000):
    import mxnet_tpu as mx
    from mxnet_tpu import models
    sym = models.get_symbol(network, num_classes=num_classes)
    arg_shapes, _, _ = sym.infer_shape(data=(batch, 3, image, image))
    out = []
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name not in ("data", "softmax_label"):
            out.append((name, tuple(shape)))
    return out


def main():
    parser = argparse.ArgumentParser(description="allreduce bandwidth")
    parser.add_argument("--network", default="resnet-50",
                        help="model whose gradient payload to reduce")
    parser.add_argument("--size-mb", type=float, default=0,
                        help="use a flat buffer of this size instead")
    parser.add_argument("--num-devices", type=int, default=0,
                        help="0 = all visible devices")
    parser.add_argument("--repeat", type=int, default=10)
    parser.add_argument("--dtype", default="float32")
    args = parser.parse_args()

    if args.num_devices and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # effective only if JAX is not initialized yet; harmless otherwise
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count="
                                   + str(args.num_devices))
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh

    devices = jax.devices()
    n = args.num_devices or len(devices)
    if len(devices) < n:
        devices = jax.devices("cpu")
    if len(devices) < n:
        raise SystemExit("need %d devices, %d visible (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count)"
                         % (n, len(devices)))
    mesh = make_mesh({"data": n}, devices[:n])

    dtype = jnp.dtype(args.dtype)
    if args.size_mb:
        shapes = [("flat", (int(args.size_mb * 2 ** 20 //
                                dtype.itemsize),))]
    else:
        shapes = grad_shapes(args.network)
    total_bytes = sum(int(np.prod(s)) for _, s in shapes) * dtype.itemsize
    print("payload: %d arrays, %.1f MB, %d devices"
          % (len(shapes), total_bytes / 2 ** 20, n))

    specs = tuple(P() for _ in shapes)

    @jax.jit
    def allreduce(*grads):
        def body(*gs):
            return tuple(jax.lax.psum(g, "data") for g in gs)
        return jax.shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)(*grads)

    rng = np.random.RandomState(0)
    grads = tuple(jnp.asarray(rng.normal(0, 1, s).astype(dtype))
                  for _, s in shapes)
    out = allreduce(*grads)          # compile + warmup
    np.asarray(out[0].ravel()[:1])   # honest completion barrier
    t0 = time.perf_counter()
    for _ in range(args.repeat):
        out = allreduce(*out)
    np.asarray(out[0].ravel()[:1])
    dt = (time.perf_counter() - t0) / args.repeat
    alg_bytes = 2.0 * (n - 1) / n * total_bytes
    print("time per allreduce: %.3f ms" % (dt * 1e3))
    print("algorithmic bandwidth: %.2f GB/s per device"
          % (alg_bytes / dt / 1e9))


if __name__ == "__main__":
    main()
