#!/usr/bin/env python
"""Benchmark the Pallas flash-attention kernel against naive XLA
attention on the real chip and write ``ATTN_BENCH.json``.

The reference has no attention op at all (SURVEY §5 long-context:
the repo predates attention models), so this artifact substantiates
the EXCEEDS-reference claim behind `examples/long-context/` with
measured numbers: tokens/s and TF/s for forward and forward+backward
at growing sequence lengths, plus where the naive path stops fitting
(its S×S score matrix is O(T²) HBM; flash never materializes it).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_one(fn, args, steps=20):
    """Chain `steps` iterations inside ONE jitted fori_loop (output fed
    back as the query so XLA cannot elide or overlap iterations), so a
    window is a single dispatch — per-call dispatch latency would
    otherwise dominate (the roofline.py method).  Median of 3
    windows; scalar-read completion barrier."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    q0, rest = args[0], args[1:]

    def chained(q, *rest):
        def body(_, q):
            out = fn(q, *rest)
            # feed a q-shaped slice of the result back in
            leaf = jax.tree_util.tree_leaves(out)[0]
            return leaf.reshape(q.shape).astype(q.dtype)
        return jnp.float32(lax.fori_loop(0, steps, body, q).sum())

    f = jax.jit(chained)
    float(f(q0, *rest))                                   # warm+sync
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(f(q0, *rest))
        times.append((time.perf_counter() - t0) / steps)
    return sorted(times)[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seqs", default="1024,2048,4096,8192")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import program
    from mxnet_tpu.op.pallas import (flash_attention,
                                     flash_attention_reference)

    program.place_compile_cache()
    b, h, d = args.batch, args.heads, args.dim
    rows = []
    for t in (int(x) for x in args.seqs.split(",")):
        rng = np.random.RandomState(0)
        shape = (b, t, h, d)        # the ring_attention layout both take
        q = jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
        k = jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
        v = jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
        # 4 matmul-shaped factors: QK^T and PV, each 2*b*h*t*t*d flops,
        # causal halves the useful triangle but the kernel still sweeps
        # blocks, so report dense flops for both (like-for-like)
        flops_fwd = 4 * b * h * t * t * d
        row = {"seq": t, "batch": b, "heads": h, "head_dim": d}

        def flash_fwd(q, k, v):
            return flash_attention(q, k, v, causal=True)

        def naive_fwd(q, k, v):
            return flash_attention_reference(q, k, v, causal=True)

        def loss(fn):
            def wrapped(q, k, v):
                return fn(q, k, v).astype(jnp.float32).sum()
            return wrapped

        def errstr(e):
            import re
            s = re.sub(r"\x1b\[[0-9;]*m", "", str(e)).split("\n")[0]
            return s[:160]

        for name, fn in (("flash", flash_fwd), ("naive", naive_fwd)):
            try:
                dt = bench_one(fn, (q, k, v), steps=args.steps)
                row["%s_fwd_ms" % name] = round(dt * 1e3, 3)
                row["%s_fwd_tflops" % name] = round(
                    flops_fwd / dt / 1e12, 1)
            except Exception as e:                      # noqa: BLE001
                row["%s_fwd_error" % name] = errstr(e)
            try:
                g = jax.grad(loss(fn), argnums=(0, 1, 2))
                dt = bench_one(g, (q, k, v), steps=max(5, args.steps // 2))
                row["%s_fwdbwd_ms" % name] = round(dt * 1e3, 3)
            except Exception as e:                      # noqa: BLE001
                row["%s_fwdbwd_error" % name] = errstr(e)
        if "flash_fwd_ms" in row and "naive_fwd_ms" in row:
            row["fwd_speedup"] = round(
                row["naive_fwd_ms"] / row["flash_fwd_ms"], 2)
        if "flash_fwdbwd_ms" in row and "naive_fwdbwd_ms" in row:
            row["fwdbwd_speedup"] = round(
                row["naive_fwdbwd_ms"] / row["flash_fwdbwd_ms"], 2)
        rows.append(row)
        print(json.dumps(row), file=sys.stderr)

    result = {"device": str(jax.devices()[0].device_kind),
              "dtype": "bfloat16", "causal": True, "rows": rows}
    out = args.out or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ATTN_BENCH.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"rows": len(rows), "out": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
