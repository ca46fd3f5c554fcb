"""Warm-restart drill for the persisted compiled-program cache.

Drives the three unified execution paths — fused Trainer step, deploy
Predictor, ModelServer bucket set — in one process against
``MXTPU_PROGRAM_CACHE`` and prints a ``PROGRAM_WARM`` JSON line with the
process-wide compile/load accounting plus numeric fingerprints of every
path's outputs.

Run it twice against one cache dir (the ci/run_tests.sh warm-cache
stage and tests/test_program.py's subprocess acceptance both do):

* first run (``--expect cold``): compiles > 0, persists > 0 — the cache
  is being filled;
* second run (``--expect warm``): **compiles == 0 and lazy traces == 0**
  — every program (trainer step, optimizer-state init, Predictor
  forward, every server bucket) deserialized from disk, and the output
  fingerprints match the cold run bit-for-bit.

Usage: python tests/nightly/program_warm.py [--expect cold|warm] [--json PATH]
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def build_symbol(mx):
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.symbol.SoftmaxOutput(net, name="softmax")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--expect", choices=("cold", "warm", "none"),
                    default="none",
                    help="assert the cache behavior of this run")
    ap.add_argument("--json", default=None,
                    help="also write the result object to this path")
    ap.add_argument("--ref", default=None,
                    help="a prior run's --json output: FAIL unless "
                         "this run's output fingerprints match it "
                         "bit-for-bit (the warm gate's wrong-program "
                         "guard)")
    args = ap.parse_args(argv)

    if not os.environ.get("MXTPU_PROGRAM_CACHE"):
        raise SystemExit("set MXTPU_PROGRAM_CACHE to the shared cache "
                         "dir before running the warm-restart drill")

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import program, serving
    from mxnet_tpu.parallel.trainer import Trainer
    from mxnet_tpu.predictor import Predictor

    sym = build_symbol(mx)
    rng = np.random.RandomState(0)
    wall = {}

    # --- trainer path: bind + init + 3 fused steps --------------------
    t0 = time.perf_counter()
    trainer = Trainer(sym, mx.optimizer.create("sgd", learning_rate=0.1,
                                               momentum=0.9))
    trainer.bind(data_shapes={"data": (8, 16)},
                 label_shapes={"softmax_label": (8,)})
    mx.random.seed(7)
    trainer.init_params(mx.init.Xavier())
    batch = {"data": mx.nd.array(rng.randn(8, 16).astype("f")),
             "softmax_label": mx.nd.array(
                 rng.randint(0, 4, 8).astype("f"))}
    for _ in range(3):
        outs = trainer.step(batch)
    train_fp = float(np.asarray(
        trainer.params["fc1_weight"]).astype(np.float64).sum())
    wall["trainer_s"] = round(time.perf_counter() - t0, 3)

    # --- predictor path: save a checkpoint, load it back --------------
    t0 = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="mxtpu-program-warm-")
    prefix = os.path.join(workdir, "model")
    arg_params, aux_params = trainer.get_params()
    mx.model.save_checkpoint(prefix, 1, sym, arg_params, aux_params)
    pred = Predictor.from_checkpoint(prefix, 1,
                                     input_shapes={"data": (2, 16)})
    pred_out = pred.predict(data=rng.randn(2, 16).astype("f"))[0]
    pred_fp = float(np.asarray(pred_out).astype(np.float64).sum())
    wall["predictor_s"] = round(time.perf_counter() - t0, 3)

    # --- serving path: 2-bucket AOT start + one padded request --------
    t0 = time.perf_counter()
    srv = serving.ModelServer(buckets=[1, 4], max_wait_us=500)
    srv.add_model("m", sym, arg_params, aux_params,
                  input_shapes={"data": (16,)})
    srv.start()
    wall["server_start_s"] = round(time.perf_counter() - t0, 3)
    serve_out = srv.predict(data=rng.randn(2, 16).astype("f"))[0]
    serve_fp = float(np.asarray(serve_out).astype(np.float64).sum())
    srv.assert_no_retrace()
    warmup_loaded = srv.stats()["warmup_loaded"]
    srv.stop()

    stats = program.cache_stats()
    result = {
        "expect": args.expect,
        "wall": wall,
        "compiles": stats["compiles"],
        "loads": stats["loads"],
        "persists": stats["persists"],
        "traces": stats["traces"],
        "retraces": stats["retraces"],
        "cache_stale": stats["cache_stale"],
        "warmup_loaded": warmup_loaded,
        "fingerprints": {"trainer": train_fp, "predictor": pred_fp,
                         "serving": serve_fp},
    }
    print("PROGRAM_WARM " + json.dumps(result))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)

    if args.expect == "cold" and stats["compiles"] == 0:
        raise SystemExit("cold run expected to compile, but compiled "
                         "nothing — is the cache dir stale?")
    if args.expect == "warm":
        if stats["compiles"] != 0 or stats["traces"] != 0:
            raise SystemExit(
                "warm run recompiled: compiles=%d traces=%d (loads=%d "
                "stale=%d) — the persisted program cache missed"
                % (stats["compiles"], stats["traces"], stats["loads"],
                   stats["cache_stale"]))
        if stats["loads"] == 0:
            raise SystemExit("warm run loaded nothing from the cache")
    if args.ref:
        with open(args.ref) as f:
            ref = json.load(f)
        if ref["fingerprints"] != result["fingerprints"]:
            raise SystemExit(
                "output fingerprints DIVERGE from the reference run: "
                "%s vs %s — a loaded executable computed something "
                "different (wrong-program execution)"
                % (result["fingerprints"], ref["fingerprints"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
