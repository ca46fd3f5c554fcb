"""Continuous-batching serving layer (``mxnet_tpu/serving/``): bucket
padding parity, zero-retrace steady state across mixed request shapes,
per-request fault isolation/timeouts, multi-tenant hosting, the keyed
compiled-forward cache, predictor dtype honoring, and the
``serve-shape-bucket`` lint pass."""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving.compiled import CompiledForward
from mxnet_tpu.serving.server import ServeError, ServeTimeout


@pytest.fixture(autouse=True)
def _fresh_cache():
    """The compiled-forward cache is process-wide and keyed on the
    symbol DIGEST: two tests building the same tiny MLP would share one
    trace log, polluting each other's retrace/lint accounting."""
    serving.clear_cache()
    yield
    serving.clear_cache()


def _close(a, b):
    """Cross-batch-size value check: a request served at bucket size B
    vs its exact-shape reference — XLA picks different kernels per
    batch (GEMV vs GEMM), so agreement is to rounding, not bitwise
    (bitwise holds pad-vs-unpadded at matching kernels — the strict
    padding-parity test)."""
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-30)


def _mlp(din=8, hidden=16, nclass=4, name="softmax", seed=0):
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=hidden, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=nclass, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name=name)
    rng = np.random.RandomState(seed)
    args = {"fc1_weight": mx.nd.array(rng.randn(hidden, din).astype("f")),
            "fc1_bias": mx.nd.array(rng.randn(hidden).astype("f")),
            "fc2_weight": mx.nd.array(rng.randn(nclass, hidden).astype("f")),
            "fc2_bias": mx.nd.array(rng.randn(nclass).astype("f"))}
    return sym, args, (din,)


def _server(sym, args, example, **kw):
    kw.setdefault("buckets", [1, 2, 4, 8])
    kw.setdefault("max_wait_us", 1000)
    srv = serving.ModelServer(**kw)
    srv.add_model("m", sym, args, {}, input_shapes={"data": example})
    return srv


def _reference(srv, x, model="m", label="softmax_label"):
    """Per-request UNPADDED forward through a FRESH CompiledForward
    (same weights, exact shape, not the server's cached instance — its
    traces must not pollute the server's retrace accounting)."""
    m = srv._models[model]
    cf = CompiledForward(m.symbol, list(m.example_shapes)
                         + list(m.label_trailing))
    feed = {"data": x.astype(m.input_dtypes["data"]),
            label: np.zeros((x.shape[0],), m.input_dtypes[label])}
    return [np.asarray(o) for o in cf.run(m.params, m.aux, feed)]


# ----------------------------------------------------------------------
def test_padding_parity_every_bucket():
    """Padded-bucket outputs are BIT-IDENTICAL to the per-request
    unpadded forward, for every bucket size, full and part-filled."""
    sym, args, example = _mlp()
    with _server(sym, args, example) as srv:
        for bucket in srv.buckets:
            for n in {bucket, max(1, bucket - 1)}:
                x = np.random.RandomState(bucket * 10 + n) \
                    .randn(n, *example).astype("f")
                got = srv.predict(data=x)
                ref = _reference(srv, x)
                assert len(got) == len(ref)
                for g, r in zip(got, ref):
                    assert g.dtype == r.dtype
                    np.testing.assert_array_equal(g, r)
        srv.assert_no_retrace()


def test_coalesced_batch_parity_and_occupancy():
    """Concurrent requests coalesce into ONE padded batch; each future
    gets exactly its own rows back."""
    sym, args, example = _mlp()
    # the cycle opens when the third submit brings the rows to cap, not
    # when a window the three have to land in runs out: on a loaded
    # machine any window is too short once
    with _server(sym, args, example, max_wait_us=20_000_000,
                 cap=6) as srv:
        xs = [np.random.RandomState(i).randn(i + 1, *example).astype("f")
              for i in range(3)]                       # rows 1 + 2 + 3 = 6
        futs = [srv.submit(data=x) for x in xs]
        outs = [f.result(20) for f in futs]
        st = srv.stats()
        assert st["batches"] == 1                      # one cycle
        assert st["occupancy"] == {"8": {"batches": 1,
                                         "mean_fill": 0.75}}
        for x, o in zip(xs, outs):
            _close(o[0], _reference(srv, x)[0])
        srv.assert_no_retrace()


def test_mixed_shape_load_zero_retrace():
    """The acceptance gate: a threaded mixed-shape load keeps the
    retrace count at the AOT warmup number (zero beyond it)."""
    sym, args, example = _mlp()
    with _server(sym, args, example) as srv:
        aot = srv.stats()["aot_compiles"]
        rng = np.random.RandomState(7)
        results = {}

        def client(cid):
            r = np.random.RandomState(cid)
            for j in range(6):
                n = int(r.randint(1, 5))
                x = r.randn(n, *example).astype("f")
                out = srv.predict(data=x)
                results[(cid, j)] = (x, out)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        st = srv.stats()
        assert st["completed"] == 24 and st["failed"] == 0
        assert st["aot_compiles"] == aot
        assert st["retraces"] == 0
        srv.assert_no_retrace()
        for x, out in results.values():
            _close(out[0], _reference(srv, x)[0])


def test_oversized_request_falls_back_and_lints():
    """A request larger than the biggest bucket still completes (exact-
    shape fallback) but is COUNTED as a retrace and flagged by the
    serve-shape-bucket pass."""
    sym, args, example = _mlp()
    with _server(sym, args, example, buckets=[1, 2, 4]) as srv:
        # clean server lints clean
        assert srv.lint().counts() == {"error": 0, "warn": 0, "info": 0}
        x = np.random.RandomState(3).randn(6, *example).astype("f")
        out = srv.predict(data=x)
        # exact-shape fallback: the SAME batch size as the reference
        np.testing.assert_array_equal(out[0], _reference(srv, x)[0])
        st = srv.stats()
        assert st["retraces"] == 1
        with pytest.raises(MXNetError, match="off-bucket"):
            srv.assert_no_retrace()
        report = srv.lint()
        assert report.counts()["warn"] == 1
        f = report.warnings()[0]
        assert f.rule == "serve-shape-bucket" and f.node == "m"
        assert "[6]" in f.message


def test_poison_request_fails_alone():
    """Error isolation: the poisoned request's future fails; the other
    requests IN THE SAME BATCH complete with correct values."""
    sym, args, example = _mlp()
    # cap = the three rows: the third submit opens the cycle (see above)
    with _server(sym, args, example, max_wait_us=20_000_000,
                 cap=3) as srv:
        xs = [np.random.RandomState(i).randn(1, *example).astype("f")
              for i in range(3)]
        with faults.injected("poison_request@request=2"):
            futs = [srv.submit(data=x) for x in xs]
            excs = [f.exception(timeout=20) for f in futs]
        assert excs[0] is None and excs[2] is None
        assert isinstance(excs[1], ServeError)
        assert "batch was unaffected" in str(excs[1])
        st = srv.stats()
        assert st["batches"] == 1          # ONE batch served all three
        assert st["completed"] == 2 and st["failed"] == 1
        for i in (0, 2):
            out = futs[i].result(timeout=20)
            assert np.all(np.isfinite(out[0]))
            _close(out[0], _reference(srv, xs[i])[0])


def test_slow_request_stretches_only_its_cycle(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_SLOW_S", "0.05")
    sym, args, example = _mlp()
    with _server(sym, args, example) as srv:
        with faults.injected("slow_request@request=1"):
            t0 = time.perf_counter()
            f1 = srv.submit(data=np.zeros(example, "f"))
            f1.result(20)
            slow_lat = time.perf_counter() - t0
            f2 = srv.submit(data=np.zeros(example, "f"))
            f2.result(20)
            # read the fired count INSIDE the scope — injected()
            # restores the previous directives on exit
            assert faults.fired("slow_request") == 1
        assert slow_lat >= 0.05
        assert srv.stats()["failed"] == 0


def test_request_timeout_fails_before_dispatch():
    sym, args, example = _mlp()
    # coalescing window far beyond the deadline: the request must be
    # timed out by the scheduler, not served late
    with _server(sym, args, example, max_wait_us=2_000_000,
                 cap=64, timeout_ms=40) as srv:
        fut = srv.submit(data=np.zeros(example, "f"))
        exc = fut.exception(timeout=20)
        assert isinstance(exc, ServeTimeout)
        st = srv.stats()
        assert st["timeouts"] == 1 and st["batches"] == 0


def test_multi_tenant_two_symbols_one_server():
    sym_a, args_a, ex_a = _mlp(din=8, hidden=16, nclass=4, seed=0)
    sym_b, args_b, ex_b = _mlp(din=5, hidden=12, nclass=3, name="out",
                               seed=1)
    srv = serving.ModelServer(buckets=[1, 2, 4], max_wait_us=1000)
    srv.add_model("a", sym_a, args_a, {}, input_shapes={"data": ex_a})
    srv.add_model("b", sym_b, args_b, {}, input_shapes={"data": ex_b})
    with srv:
        with pytest.raises(MXNetError, match="multi-tenant"):
            srv.submit(data=np.zeros(ex_a, "f"))
        xa = np.random.RandomState(0).randn(2, *ex_a).astype("f")
        xb = np.random.RandomState(1).randn(3, *ex_b).astype("f")
        fa = srv.submit(data=xa, model="a")
        fb = srv.submit(data=xb, model="b")
        oa, ob = fa.result(20), fb.result(20)
        assert oa[0].shape == (2, 4) and ob[0].shape == (3, 3)
        _close(oa[0], _reference(srv, xa, model="a")[0])
        _close(ob[0], _reference(srv, xb, model="b",
                                 label="out_label")[0])
        srv.assert_no_retrace()


def test_submit_validation_errors():
    sym, args, example = _mlp()
    srv = _server(sym, args, example)
    with pytest.raises(MXNetError, match="not started"):
        srv.submit(data=np.zeros(example, "f"))
    with srv:
        with pytest.raises(MXNetError, match="matches neither"):
            srv.submit(data=np.zeros((3,), "f"))
        with pytest.raises(MXNetError, match="missing input"):
            srv.submit(other=np.zeros(example, "f"))
        with pytest.raises(MXNetError, match="unknown model"):
            srv.submit(data=np.zeros(example, "f"), model="nope")
        with pytest.raises(MXNetError, match="add_model before start"):
            srv.add_model("late", sym, args, {},
                          input_shapes={"data": example})


# ----------------------------------------------------------------------
def _checkpoint(tmp_path, dtype="float32"):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=5,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    args = {
        "fc_weight": mx.nd.array(rng.normal(0, 1, (5, 8)).astype("f"))
        .astype(dtype),
        "fc_bias": mx.nd.array(rng.normal(0, 1, (5,)).astype("f"))
        .astype(dtype)}
    prefix = str(tmp_path / ("m_" + dtype))
    mx.model.save_checkpoint(prefix, 1, net, args, {})
    return prefix


def test_compiled_forward_cache_shared_across_predictors(tmp_path):
    """from_checkpoint of an already-loaded model compiles NOTHING: the
    keyed cache hands both predictors the same CompiledForward."""
    from mxnet_tpu.predictor import Predictor
    prefix = _checkpoint(tmp_path)
    p1 = Predictor.from_checkpoint(prefix, 1, {"data": (2, 8)})
    x = np.random.RandomState(1).randn(2, 8).astype("f")
    out1 = p1.predict(data=x)[0]
    traces = serving.cache_stats()["traces"]
    p2 = Predictor.from_checkpoint(prefix, 1, {"data": (2, 8)})
    assert p2._cf is p1._cf
    assert serving.cache_stats()["traces"] == traces   # zero new compiles
    np.testing.assert_array_equal(out1, p2.predict(data=x)[0])


def test_predictor_honors_bound_dtype(tmp_path):
    """A bf16 checkpoint binds bf16 inputs and returns bf16 outputs —
    no silent f32 round-trip (satellite: predictor.py:107,126)."""
    import jax.numpy as jnp
    from mxnet_tpu.predictor import Predictor
    bf16 = np.dtype(jnp.bfloat16)
    prefix = _checkpoint(tmp_path, dtype="bfloat16")
    p = Predictor.from_checkpoint(prefix, 1, {"data": (2, 8)})
    assert p.input_dtype("data") == bf16
    x = np.random.RandomState(1).randn(2, 8).astype("f")
    p.set_input("data", x)
    assert p._inputs["data"].dtype == bf16
    p.forward()
    out = p.get_output(0)
    assert out.dtype == bf16
    np.testing.assert_allclose(
        np.asarray(out, np.float32).sum(axis=1), [1.0, 1.0], rtol=2e-2)
    # f32 checkpoints keep the f32 contract (the C ABI's surface)
    p32 = Predictor.from_checkpoint(_checkpoint(tmp_path), 1,
                                    {"data": (2, 8)})
    assert p32.input_dtype("data") == np.float32
    assert p32.predict(data=x)[0].dtype == np.float32


def test_server_serves_bf16_model_in_bf16(tmp_path):
    """The serving path inherits the inferred dtype: a bf16 model's
    buckets stage and return bf16."""
    import jax.numpy as jnp
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=5,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    args = {"fc_weight": mx.nd.array(rng.randn(5, 8).astype("f"))
            .astype("bfloat16"),
            "fc_bias": mx.nd.array(np.zeros(5, "f")).astype("bfloat16")}
    srv = serving.ModelServer(buckets=[1, 2], max_wait_us=1000)
    srv.add_model("m", net, args, {}, input_shapes={"data": (8,)})
    with srv:
        assert srv._models["m"].input_dtypes["data"] == \
            np.dtype(jnp.bfloat16)
        out = srv.predict(data=rng.randn(8).astype("f"))
        assert out[0].dtype == np.dtype(jnp.bfloat16)
        # error isolation must hold for bf16 too (np.issubdtype does
        # not class bfloat16 as floating — the check uses jnp's)
        bad = np.full((8,), np.nan, np.float32)
        exc = srv.submit(data=bad).exception(timeout=20)
        assert isinstance(exc, ServeError)
        srv.assert_no_retrace()


def test_multi_tenant_shared_symbol_no_double_count():
    """Two checkpoints of ONE architecture share a CompiledForward;
    retrace/AOT accounting must count it once, not per tenant."""
    sym_a, args_a, example = _mlp(seed=0)
    _, args_b, _ = _mlp(seed=9)
    srv = serving.ModelServer(buckets=[1, 2, 4], max_wait_us=1000)
    srv.add_model("a", sym_a, args_a, {}, input_shapes={"data": example})
    srv.add_model("b", sym_a, args_b, {}, input_shapes={"data": example})
    assert srv._models["a"].cf is srv._models["b"].cf
    with srv:
        assert srv.stats()["aot_compiles"] == 3      # once, not twice
        x = np.random.RandomState(0).randn(6, *example).astype("f")
        srv.predict(data=x, model="a")               # oversized: 1 retrace
        assert srv.stats()["retraces"] == 1
        report = srv.lint()
        assert report.counts()["warn"] == 1          # one finding, joined
        assert report.warnings()[0].node == "a+b"
        # the two tenants still serve their own weights
        oa = srv.predict(data=x[:2], model="a")
        ob = srv.predict(data=x[:2], model="b")
        assert not np.array_equal(oa[0], ob[0])


def test_mesh_rejects_indivisible_buckets():
    import jax
    from mxnet_tpu import parallel
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = parallel.make_mesh({"data": 2}, devices[:2])
    with pytest.raises(MXNetError, match="not divisible"):
        serving.ModelServer(buckets=[1, 4, 8], mesh=mesh)


def test_submit_after_stop_raises():
    sym, args, example = _mlp()
    srv = _server(sym, args, example)
    srv.start()
    srv.stop()
    with pytest.raises(MXNetError, match="not started"):
        srv.submit(data=np.zeros(example, "f"))


def test_mesh_sharded_serving():
    """Weights placed once replicated on a mesh, batches row-sharded
    along the data axis (the trainer's placement machinery) — and the
    AOT signatures still match: zero retraces."""
    import jax
    from mxnet_tpu import parallel
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = parallel.make_mesh({"data": 2}, devices[:2])
    sym, args, example = _mlp()
    srv = serving.ModelServer(buckets=[2, 4, 8], max_wait_us=1000,
                              mesh=mesh)
    srv.add_model("m", sym, args, {}, input_shapes={"data": example})
    with srv:
        for n in (1, 2, 3):
            x = np.random.RandomState(n).randn(n, *example).astype("f")
            out = srv.predict(data=x)
            np.testing.assert_allclose(
                out[0], _reference(srv, x)[0], rtol=1e-6, atol=1e-7)
        srv.assert_no_retrace()
        # oversized fallback on a mesh: the pad keeps the row-sharded
        # batch dim divisible by the data axis (9 rows -> 10)
        x = np.random.RandomState(9).randn(9, *example).astype("f")
        out = srv.predict(data=x)
        assert out[0].shape[0] == 9
        assert srv.stats()["retraces"] == 1


def test_lint_server_registered_in_cli_targets():
    """The serving lint target is wired into the gate (baseline entry
    exists, pass is registered)."""
    from mxnet_tpu import analysis
    assert "serve-shape-bucket" in analysis.list_passes("jaxpr")
    baseline = analysis.load_baseline()
    assert baseline is not None and "serving" in baseline
    assert baseline["serving"]["error"] == 0
