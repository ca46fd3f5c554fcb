#!/usr/bin/env python
"""Matrix-factorization recommender, toy-sized (reference
``example/recommenders/``): user and item ``Embedding`` tables whose
dot product predicts ratings, trained with
``LinearRegressionOutput`` on (user, item, rating) triplets — the
two-embedding interaction pattern (broadcast multiply + reduce) no
other example trains.

Run: python examples/recommenders/matrix_fact_toy.py
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

USERS, ITEMS, RANK = 40, 30, 6


def mf_symbol(rank=RANK):
    user = mx.sym.Variable("user")
    item = mx.sym.Variable("item")
    score = mx.sym.Variable("score_label")
    u = mx.sym.Embedding(user, input_dim=USERS, output_dim=rank,
                         name="user_embed")          # (B, 1, R)
    v = mx.sym.Embedding(item, input_dim=ITEMS, output_dim=rank,
                         name="item_embed")
    u = mx.sym.Flatten(u)
    v = mx.sym.Flatten(v)
    pred = mx.sym.sum(u * v, axis=1, keepdims=True)  # (B, 1)
    return mx.sym.LinearRegressionOutput(pred, score, name="score")


def make_data(rng, n=2048):
    """Ratings from a hidden low-rank factorization + noise."""
    U = rng.normal(0, 1, (USERS, RANK)).astype("f")
    V = rng.normal(0, 1, (ITEMS, RANK)).astype("f")
    users = rng.randint(0, USERS, n).astype("f")
    items = rng.randint(0, ITEMS, n).astype("f")
    scores = (U[users.astype(int)] * V[items.astype(int)]).sum(1)
    scores += rng.normal(0, 0.05, n).astype("f")
    return users.reshape(-1, 1), items.reshape(-1, 1), \
        scores.astype("f").reshape(-1, 1)


def main(epochs=20, batch=64):
    logging.basicConfig(level=logging.INFO)
    rng = np.random.RandomState(0)
    users, items, scores = make_data(rng)
    it = mx.io.NDArrayIter({"user": users, "item": items},
                           {"score_label": scores},
                           batch_size=batch, shuffle=True)
    mod = mx.mod.Module(mf_symbol(), context=mx.cpu(),
                        data_names=("user", "item"),
                        label_names=("score_label",))
    mod.fit(it, num_epoch=epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.02},
            initializer=mx.init.Normal(0.3), eval_metric="rmse")
    it.reset()
    metric = mx.metric.create("rmse")
    for b in it:
        mod.forward(b, is_train=False)
        metric.update(b.label, mod.get_outputs())
    return metric.get()[1]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=20)
    args = ap.parse_args()
    rmse = main(epochs=args.epochs)
    # hidden factors have unit scale: ratings have std ~ sqrt(RANK); an
    # unlearned model reads rmse ~ 2.4, the noise floor is 0.05
    assert rmse < 0.5, rmse
    print("matrix-factorization toy OK: rmse %.3f" % rmse)
