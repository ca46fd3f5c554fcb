#!/usr/bin/env python
"""Deep Embedded Clustering, toy-sized (reference ``example/dec/dec.py``):
autoencoder-pretrained encoder + k-means-initialized centroids, then
self-training on the KL(P||Q) clustering objective where Q is the
Student-t soft assignment of embeddings to centroids and P is the
sharpened target distribution, refreshed every ``update_interval``.

The reference implemented Q and its hand-derived gradient as a
``NumpyOp``; here the whole DEC layer is built from registry ops
(broadcast distance, power, normalize) under ``MakeLoss``, so the
gradient — including the centroid gradient — comes from autodiff and
the loss compiles into the training graph.  This is the only example
that trains ``MakeLoss`` and a *learned parameter initialized from a
host-side algorithm* (k-means) end-to-end.  On this low-dimensional
toy k-means already lands near the optimum; the assertions check the
self-training loop reaches high accuracy and never regresses it (the
paper's gains need high-dimensional data where k-means is weak).

Run: python examples/dec/dec_toy.py
"""
import argparse
import logging
import os
import sys

# tiny-batch toy: latency-bound, not compute-bound — it selects the CPU
# backend unless MXTPU_TOY_BACKEND says otherwise
if os.environ.get("MXTPU_TOY_BACKEND", "cpu") == "cpu":
    import jax
    jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np

import mxnet_tpu as mx

DIM, LATENT, CENTERS, ALPHA = 16, 2, 3, 1.0


def encoder_symbol():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=16, name="enc1")
    h = mx.sym.Activation(h, act_type="relu")
    return mx.sym.FullyConnected(h, num_hidden=LATENT, name="enc2")


def dec_symbol():
    """Encoder -> Student-t soft assignment Q -> KL(P||Q) via MakeLoss
    (reference DECLoss.forward/backward, autodiffed)."""
    z = encoder_symbol()                                   # (B, L)
    mu = mx.sym.Variable("dec_mu_weight", shape=(CENTERS, LATENT))
    p = mx.sym.Variable("p_label")                         # (B, C)
    zb = mx.sym.Reshape(z, shape=(-1, 1, LATENT))
    mub = mx.sym.Reshape(mu, shape=(1, CENTERS, LATENT))
    dist2 = mx.sym.sum(mx.sym.square(mx.sym.broadcast_sub(zb, mub)),
                       axis=2)                             # (B, C)
    qu = (1.0 + dist2 / ALPHA) ** (-(ALPHA + 1.0) / 2.0)
    q = mx.sym.broadcast_div(qu, mx.sym.sum(qu, axis=1, keepdims=True))
    kl = mx.sym.sum(p * (mx.sym.log(p + 1e-6) - mx.sym.log(q + 1e-6)))
    loss = mx.sym.MakeLoss(kl, name="dec")
    # Group so forward exposes Q for assignment reads AND the loss;
    # BlockGrad keeps the Q head out of the backward
    return mx.sym.Group([mx.sym.BlockGrad(q), loss])


def target_distribution(q):
    """P = sharpened Q with per-cluster frequency normalization
    (reference refresh())."""
    w = (q ** 2) / q.sum(0)
    return (w.T / w.sum(1)).T


def kmeans(z, k, rng, iters=20):
    centers = z[rng.choice(len(z), k, replace=False)]
    for _ in range(iters):
        assign = ((z[:, None] - centers[None]) ** 2).sum(-1).argmin(1)
        for j in range(k):
            if (assign == j).any():
                centers[j] = z[assign == j].mean(0)
    return centers


def cluster_acc(pred, truth):
    """Best one-to-one label matching (reference ``cluster_acc``)."""
    from itertools import permutations
    best = 0.0
    for perm in permutations(range(CENTERS)):
        mapped = np.asarray(perm)[pred]
        best = max(best, (mapped == truth).mean())
    return best


def make_data(rng, n=300):
    """Three well-separated Gaussian blobs pushed through a random
    linear map into DIM dimensions."""
    means = np.asarray([[0, 0], [2.2, 2.2], [0, 2.8]], "f")
    y = rng.randint(0, CENTERS, n)
    lat = means[y] + rng.normal(0, 0.55, (n, 2)).astype("f")
    proj = rng.normal(0, 1, (2, DIM)).astype("f")
    return (lat @ proj + rng.normal(0, 0.05, (n, DIM))).astype("f"), y


def pretrain_encoder(x, epochs=30):
    """Quick autoencoder pretrain; returns the encoder arg_params."""
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=16, name="enc1")
    h = mx.sym.Activation(h, act_type="relu")
    z = mx.sym.FullyConnected(h, num_hidden=LATENT, name="enc2")
    h = mx.sym.FullyConnected(z, num_hidden=16, name="dec1")
    h = mx.sym.Activation(h, act_type="relu")
    out = mx.sym.FullyConnected(h, num_hidden=DIM, name="dec2")
    ae = mx.sym.LinearRegressionOutput(out, mx.sym.Variable("rec_label"),
                                       name="rec")
    it = mx.io.NDArrayIter(x, x.copy(), batch_size=32, shuffle=True,
                           label_name="rec_label")
    mod = mx.mod.Module(ae, label_names=("rec_label",), context=mx.cpu())
    mod.fit(it, num_epoch=epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier())
    return dict(mod.get_params()[0])


def main(update_interval=4, rounds=40):
    logging.basicConfig(level=logging.INFO)
    rng = np.random.RandomState(0)
    x, y = make_data(rng)
    ae_args = pretrain_encoder(x)

    # encoder features -> k-means centroid init (reference cluster())
    enc = encoder_symbol()
    ex = enc.bind(mx.cpu(), args={
        "data": mx.nd.array(x),
        **{k: mx.nd.array(v.asnumpy()) for k, v in ae_args.items()
           if k.startswith("enc")}})
    z = ex.forward()[0].asnumpy()
    mu0 = kmeans(z, CENTERS, rng)

    mod = mx.mod.Module(dec_symbol(), context=mx.cpu(),
                        label_names=("p_label",))
    batch = len(x)                     # full-batch toy, like the paper's P
    mod.bind(data_shapes=[("data", (batch, DIM))],
             label_shapes=[("p_label", (batch, CENTERS))])
    mod.init_params(mx.init.Xavier())
    mod.set_params({**{k: mx.nd.array(v.asnumpy()) for k, v in
                       ae_args.items() if k.startswith("enc")},
                    "dec_mu_weight": mx.nd.array(mu0)},
                   {}, allow_missing=True)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / batch})

    if rounds < 1:
        raise SystemExit("--rounds must be >= 1")
    p = None
    for r in range(rounds):
        dummy = mx.io.DataBatch(
            data=[mx.nd.array(x)],
            label=[mx.nd.array(p if p is not None
                               else np.ones((batch, CENTERS), "f")
                               / CENTERS)], pad=0)
        if r % update_interval == 0:
            mod.forward(dummy, is_train=False)
            q = mod.get_outputs()[0].asnumpy()
            p = target_distribution(q).astype("f")
            acc = cluster_acc(q.argmax(1), y)
            if r == 0:
                acc0 = acc
            logging.info("round %d cluster acc %.3f", r, acc)
            dummy = mx.io.DataBatch(data=[mx.nd.array(x)],
                                    label=[mx.nd.array(p)], pad=0)
        mod.forward(dummy, is_train=True)
        mod.backward()
        mod.update()

    mod.forward(mx.io.DataBatch(data=[mx.nd.array(x)],
                                label=[mx.nd.array(p)], pad=0),
                is_train=False)
    q = mod.get_outputs()[0].asnumpy()
    return acc0, cluster_acc(q.argmax(1), y)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    args = ap.parse_args()
    acc0, acc = main(rounds=args.rounds)
    assert acc > 0.9, (acc0, acc)
    assert acc >= acc0, (acc0, acc)   # self-training must not regress
    print("dec toy OK: cluster acc %.3f -> %.3f" % (acc0, acc))
