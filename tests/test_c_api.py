"""Native C predict API (the reference's ``c_predict_api.h`` surface,
built as ``libmxtpu_c_api.so``) driven via ctypes, plus the python
Predictor it wraps."""
import ctypes
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.predictor import Predictor

_LIB = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "mxnet_tpu", "lib", "libmxtpu_c_api.so")


def _make_checkpoint(tmp_path):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=5,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    args = {"fc_weight": mx.nd.array(rng.normal(0, 1, (5, 8)).astype("f")),
            "fc_bias": mx.nd.array(rng.normal(0, 1, (5,)).astype("f"))}
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 3, net, args, {})
    return prefix, rng


def test_python_predictor(tmp_path):
    prefix, rng = _make_checkpoint(tmp_path)
    p = Predictor.from_checkpoint(prefix, 3, {"data": (2, 8)})
    x = rng.normal(0, 1, (2, 8)).astype("f")
    out = p.predict(data=x)[0]
    assert out.shape == (2, 5)
    np.testing.assert_allclose(out.sum(axis=1), [1.0, 1.0], rtol=1e-5)
    # deterministic across calls
    out2 = p.predict(data=x)[0]
    np.testing.assert_allclose(out, out2)


def test_predictor_rejects_bad_input(tmp_path):
    prefix, rng = _make_checkpoint(tmp_path)
    p = Predictor.from_checkpoint(prefix, 3, {"data": (2, 8)})
    with pytest.raises(Exception):
        p.set_input("data", np.zeros((3, 8), "f"))
    with pytest.raises(Exception):
        p.set_input("nope", np.zeros((2, 8), "f"))


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_c_predict_api(tmp_path):
    prefix, rng = _make_checkpoint(tmp_path)
    with open(prefix + "-symbol.json") as f:
        sym_json = f.read().encode()
    with open(prefix + "-0003.params", "rb") as f:
        params = f.read()

    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p

    handle = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape_data = (ctypes.c_uint * 2)(2, 8)
    rc = lib.MXPredCreate(ctypes.c_char_p(sym_json), params, len(params),
                          1, 0, 1, keys, indptr, shape_data,
                          ctypes.byref(handle))
    assert rc == 0, lib.MXGetLastError()

    sd = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    rc = lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sd),
                                  ctypes.byref(ndim))
    assert rc == 0, lib.MXGetLastError()
    out_shape = tuple(sd[i] for i in range(ndim.value))
    assert out_shape == (2, 5)

    x = rng.normal(0, 1, (2, 8)).astype("f")
    rc = lib.MXPredSetInput(handle, b"data",
                            x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                            x.size)
    assert rc == 0, lib.MXGetLastError()
    rc = lib.MXPredForward(handle)
    assert rc == 0, lib.MXGetLastError()

    out = np.zeros((2, 5), "f")
    rc = lib.MXPredGetOutput(
        handle, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size)
    assert rc == 0, lib.MXGetLastError()

    expect = Predictor.from_checkpoint(prefix, 3,
                                       {"data": (2, 8)}).predict(data=x)[0]
    np.testing.assert_allclose(out, expect, rtol=1e-5)
    assert lib.MXPredFree(handle) == 0


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_c_core_symbol_bind_forward():
    """Build a symbol, bind, and run forward/backward through the C ABI
    core (the reference c_api.h choke-point contract)."""
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p

    def ok(rc):
        assert rc == 0, lib.MXGetLastError()

    # data variable
    data = ctypes.c_void_p()
    ok(lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)))

    # find the FullyConnected creator
    n = ctypes.c_uint()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    ok(lib.MXSymbolListAtomicSymbolCreators(ctypes.byref(n),
                                            ctypes.byref(creators)))
    fc_creator = None
    name_p = ctypes.c_char_p()
    for i in range(n.value):
        ok(lib.MXSymbolGetAtomicSymbolName(ctypes.c_void_p(creators[i]),
                                           ctypes.byref(name_p)))
        if name_p.value == b"FullyConnected":
            fc_creator = ctypes.c_void_p(creators[i])
    assert fc_creator is not None and n.value > 200

    fc = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"3")
    ok(lib.MXSymbolCreateAtomicSymbol(fc_creator, 1, keys, vals,
                                      ctypes.byref(fc)))
    arg_keys = (ctypes.c_char_p * 1)(b"data")
    arg_vals = (ctypes.c_void_p * 1)(data)
    ok(lib.MXSymbolCompose(fc, b"fc", 1, arg_keys, arg_vals))

    # arguments round-trip
    size = ctypes.c_uint()
    strs = ctypes.POINTER(ctypes.c_char_p)()
    ok(lib.MXSymbolListArguments(fc, ctypes.byref(size), ctypes.byref(strs)))
    args = [strs[i].decode() for i in range(size.value)]
    assert args == ["data", "fc_weight", "fc_bias"]

    # JSON round trip
    json_p = ctypes.c_char_p()
    ok(lib.MXSymbolSaveToJSON(fc, ctypes.byref(json_p)))
    sym2 = ctypes.c_void_p()
    ok(lib.MXSymbolCreateFromJSON(json_p, ctypes.byref(sym2)))

    # bind: data (2,4)
    exec_h = ctypes.c_void_p()
    in_keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape_data = (ctypes.c_uint * 2)(2, 4)
    ok(lib.MXExecutorSimpleBind(fc, 1, 0, 1, in_keys, indptr, shape_data,
                                b"write", ctypes.byref(exec_h)))

    # fill args through the C ABI
    rng = np.random.RandomState(0)
    x = rng.randn(2, 4).astype("f")
    w = rng.randn(3, 4).astype("f")
    b = rng.randn(3).astype("f")
    for name, val in [(b"data", x), (b"fc_weight", w), (b"fc_bias", b)]:
        h = ctypes.c_void_p()
        ok(lib.MXExecutorGetArg(exec_h, name, ctypes.byref(h)))
        ok(lib.MXNDArraySyncCopyFromCPU(
            h, val.ctypes.data_as(ctypes.c_void_p), val.size))
        lib.MXNDArrayFree(h)

    ok(lib.MXExecutorForward(exec_h, 1))
    n_out = ctypes.c_uint()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    ok(lib.MXExecutorOutputs(exec_h, ctypes.byref(n_out),
                             ctypes.byref(outs)))
    assert n_out.value == 1
    got = np.zeros((2, 3), "f")
    ok(lib.MXNDArraySyncCopyToCPU(
        ctypes.c_void_p(outs[0]), got.ctypes.data_as(ctypes.c_void_p),
        got.size))
    np.testing.assert_allclose(got, x @ w.T + b, rtol=1e-5)

    ok(lib.MXExecutorBackward(exec_h, 0, None))
    g = ctypes.c_void_p()
    ok(lib.MXExecutorGetGrad(exec_h, b"fc_weight", ctypes.byref(g)))
    gw = np.zeros((3, 4), "f")
    ok(lib.MXNDArraySyncCopyToCPU(
        g, gw.ctypes.data_as(ctypes.c_void_p), gw.size))
    np.testing.assert_allclose(gw, np.ones((2, 3), "f").T @ x, rtol=1e-4)

    lib.MXExecutorFree(exec_h)
    lib.MXSymbolFree(fc)
    lib.MXSymbolFree(sym2)
    lib.MXSymbolFree(data)


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_c_core_imperative_and_kvstore():
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p

    def ok(rc):
        assert rc == 0, lib.MXGetLastError()

    # NDArray create + fill
    shape = (ctypes.c_uint * 2)(2, 3)
    a = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(a)))
    xs = np.arange(6, dtype="f").reshape(2, 3)
    ok(lib.MXNDArraySyncCopyFromCPU(
        a, xs.ctypes.data_as(ctypes.c_void_p), xs.size))
    dim = ctypes.c_uint()
    pshape = ctypes.POINTER(ctypes.c_uint)()
    ok(lib.MXNDArrayGetShape(a, ctypes.byref(dim), ctypes.byref(pshape)))
    assert [pshape[i] for i in range(dim.value)] == [2, 3]

    # imperative: sqrt(a + a)
    n_out = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    ins = (ctypes.c_void_p * 2)(a, a)
    ok(lib.MXImperativeInvokeByName(b"_plus", 2, ins, ctypes.byref(n_out),
                                    ctypes.byref(outs), 0, None, None))
    assert n_out.value == 1
    summed = ctypes.c_void_p(outs[0])
    ins1 = (ctypes.c_void_p * 1)(summed)
    ok(lib.MXImperativeInvokeByName(b"sqrt", 1, ins1, ctypes.byref(n_out),
                                    ctypes.byref(outs), 0, None, None))
    got = np.zeros((2, 3), "f")
    ok(lib.MXNDArraySyncCopyToCPU(
        ctypes.c_void_p(outs[0]), got.ctypes.data_as(ctypes.c_void_p),
        got.size))
    np.testing.assert_allclose(got, np.sqrt(2 * xs), rtol=1e-5)

    # kvstore local: init/push/pull through the ABI
    kv = ctypes.c_void_p()
    ok(lib.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    rank = ctypes.c_int()
    ok(lib.MXKVStoreGetRank(kv, ctypes.byref(rank)))
    assert rank.value == 0
    key = (ctypes.c_int * 1)(7)
    vals = (ctypes.c_void_p * 1)(a)
    ok(lib.MXKVStoreInit(kv, 1, key, vals))
    ok(lib.MXKVStorePush(kv, 1, key, vals, 0))
    out_nd = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(out_nd)))
    pulls = (ctypes.c_void_p * 1)(out_nd)
    ok(lib.MXKVStorePull(kv, 1, key, pulls, 0))
    pulled = np.zeros((2, 3), "f")
    ok(lib.MXNDArraySyncCopyToCPU(
        out_nd, pulled.ctypes.data_as(ctypes.c_void_p), pulled.size))
    np.testing.assert_allclose(pulled, xs)
    lib.MXKVStoreFree(kv)
    lib.MXNDArrayFree(a)
    lib.MXNDArrayFree(out_nd)


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_cpp_package_generated_wrappers():
    """Build + run the C++ example that drives the generated op wrappers
    (mxtpu_ops.hpp from tools/gen_cpp_wrappers.py) through the C ABI."""
    import shutil
    import subprocess
    import sys
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cpp = os.path.join(root, "cpp-package")
    assert os.path.exists(os.path.join(cpp, "include", "mxtpu_ops.hpp")), \
        "run tools/gen_cpp_wrappers.py"
    subprocess.run(["make", "-C", cpp], check=True, capture_output=True,
                   timeout=240)
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run([os.path.join(cpp, "ops_example")], env=env,
                         capture_output=True, text=True, timeout=240,
                         cwd=root)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ops example OK" in res.stdout


def test_wrapper_generator_is_current(tmp_path):
    """The committed mxtpu_ops.hpp must match a fresh generation run
    (registry drift would silently stale the cpp-package)."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = str(tmp_path / "ops.hpp")
    subprocess.run([sys.executable,
                    os.path.join(root, "tools", "gen_cpp_wrappers.py"),
                    "-o", out], check=True, capture_output=True,
                   timeout=240, cwd=root,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
    with open(out) as f:
        fresh = f.read()
    with open(os.path.join(root, "cpp-package", "include",
                           "mxtpu_ops.hpp")) as f:
        committed = f.read()
    assert fresh == committed, \
        "cpp-package/include/mxtpu_ops.hpp is stale; re-run " \
        "tools/gen_cpp_wrappers.py"


def test_committed_native_libraries_are_current(tmp_path):
    """``mxnet_tpu/lib/*.so`` are committed build products: they must be
    byte for byte what ``make -C native`` builds from the committed
    sources with this toolchain (an edited ``.cc`` without a rebuild
    would otherwise load as stale native code)."""
    import shutil
    import subprocess
    if not (shutil.which("make") and shutil.which("g++")):
        pytest.skip("no make/g++ here")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = {"OUT": "libmxtpu_runtime.so", "CAPI": "libmxtpu_c_api.so",
             "LOADER": "libmxtpu_dataloader.so"}
    subprocess.run(
        ["make", "-C", os.path.join(root, "native")]
        + ["%s=%s" % (var, tmp_path / name) for var, name in names.items()],
        check=True, capture_output=True, timeout=240)
    for name in names.values():
        with open(tmp_path / name, "rb") as f:
            fresh = f.read()
        with open(os.path.join(root, "mxnet_tpu", "lib", name), "rb") as f:
            assert f.read() == fresh, \
                "mxnet_tpu/lib/%s is not what native/ builds; run " \
                "`make -C native` and commit the result" % name


def _write_synth_mnist(tmp_path, n=200):
    """MNIST-format files with a learnable rule: the lit quadrant block
    encodes the class (4 classes, labels 0-3)."""
    import gzip
    import struct
    rng = np.random.RandomState(0)
    images = np.zeros((n, 28, 28), np.uint8)
    labels = (np.arange(n) % 4).astype(np.uint8)
    off = {0: (2, 2), 1: (2, 16), 2: (16, 2), 3: (16, 16)}
    for i in range(n):
        r, c = off[int(labels[i])]
        images[i, r:r + 10, c:c + 10] = 250
        images[i] += rng.randint(0, 20, (28, 28), dtype=np.uint8)
    img_path = str(tmp_path / "img-idx3-ubyte")
    lbl_path = str(tmp_path / "lbl-idx1-ubyte")
    with open(img_path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(images.tobytes())
    with open(lbl_path, "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(labels.tobytes())
    return img_path, lbl_path


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_c_dataiter_group(tmp_path):
    """MXListDataIters / MXDataIterCreateIter / Next / GetData / GetLabel
    / GetPadNum / BeforeFirst (reference c_api.h:1108-1199)."""
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p

    def ok(rc):
        assert rc == 0, lib.MXGetLastError()

    img, lbl = _write_synth_mnist(tmp_path, n=50)
    n = ctypes.c_uint()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    ok(lib.MXListDataIters(ctypes.byref(n), ctypes.byref(creators)))
    found = None
    name_p = ctypes.c_char_p()
    for i in range(n.value):
        ok(lib.MXDataIterGetIterInfo(ctypes.c_void_p(creators[i]),
                                     ctypes.byref(name_p), None, None,
                                     None, None, None))
        if name_p.value == b"MNISTIter":
            found = ctypes.c_void_p(creators[i])
    assert found is not None and n.value >= 4

    keys = (ctypes.c_char_p * 5)(b"image", b"label", b"batch_size",
                                 b"shuffle", b"silent")
    vals = (ctypes.c_char_p * 5)(img.encode(), lbl.encode(), b"16",
                                 b"False", b"True")
    it = ctypes.c_void_p()
    ok(lib.MXDataIterCreateIter(found, 5, keys, vals, ctypes.byref(it)))

    batches = 0
    total_pad = 0
    labels_seen = []
    more = ctypes.c_int()
    while True:
        ok(lib.MXDataIterNext(it, ctypes.byref(more)))
        if not more.value:
            break
        batches += 1
        d = ctypes.c_void_p()
        ok(lib.MXDataIterGetData(it, ctypes.byref(d)))
        dim = ctypes.c_uint()
        pshape = ctypes.POINTER(ctypes.c_uint)()
        ok(lib.MXNDArrayGetShape(d, ctypes.byref(dim), ctypes.byref(pshape)))
        assert [pshape[i] for i in range(dim.value)] == [16, 1, 28, 28]
        lb = ctypes.c_void_p()
        ok(lib.MXDataIterGetLabel(it, ctypes.byref(lb)))
        got = np.zeros(16, "f")
        ok(lib.MXNDArraySyncCopyToCPU(
            lb, got.ctypes.data_as(ctypes.c_void_p), got.size))
        labels_seen.append(got)
        pad = ctypes.c_int()
        ok(lib.MXDataIterGetPadNum(it, ctypes.byref(pad)))
        total_pad += pad.value
        lib.MXNDArrayFree(d)
        lib.MXNDArrayFree(lb)
    assert batches == 4 and total_pad == 14      # 50 samples, batch 16
    np.testing.assert_allclose(labels_seen[0][:4], [0, 1, 2, 3])

    # rewind replays the epoch
    ok(lib.MXDataIterBeforeFirst(it))
    ok(lib.MXDataIterNext(it, ctypes.byref(more)))
    assert more.value == 1
    lib.MXDataIterFree(it)


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_c_recordio_autograd_profiler(tmp_path):
    """RecordIO reader/writer, autograd mark/compute, profiler
    set-config/dump through the C ABI (c_api.h:1408-1466, :539-558,
    :183-194)."""
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p

    def ok(rc):
        assert rc == 0, lib.MXGetLastError()

    # --- RecordIO round-trip
    uri = str(tmp_path / "t.rec")
    w = ctypes.c_void_p()
    ok(lib.MXRecordIOWriterCreate(uri.encode(), ctypes.byref(w)))
    # includes a zero-length record: valid, distinct from end-of-stream
    payloads = [b"hello", b"", b"x" * 1000, b"\x0a\x23\xd7\xce" * 8]
    for p in payloads:
        ok(lib.MXRecordIOWriterWriteRecord(w, p, len(p)))
    pos = ctypes.c_size_t()
    ok(lib.MXRecordIOWriterTell(w, ctypes.byref(pos)))
    assert pos.value > 0
    ok(lib.MXRecordIOWriterFree(w))

    r = ctypes.c_void_p()
    ok(lib.MXRecordIOReaderCreate(uri.encode(), ctypes.byref(r)))
    got = []
    while True:
        buf = ctypes.c_void_p()
        size = ctypes.c_size_t()
        ok(lib.MXRecordIOReaderReadRecord(r, ctypes.byref(buf),
                                          ctypes.byref(size)))
        if not buf.value:                # EOF = null buffer
            break
        got.append(ctypes.string_at(buf.value, size.value))
    assert got == payloads
    ok(lib.MXRecordIOReaderFree(r))

    # --- autograd: d(sum(x*x))/dx = 2x
    shape = (ctypes.c_uint * 1)(4)
    x = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(x)))
    xs = np.array([1.0, 2.0, 3.0, 4.0], "f")
    ok(lib.MXNDArraySyncCopyFromCPU(
        x, xs.ctypes.data_as(ctypes.c_void_p), xs.size))
    g = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(g)))

    prev = ctypes.c_int()
    ok(lib.MXAutogradSetIsTraining(1, ctypes.byref(prev)))
    var_h = (ctypes.c_void_p * 1)(x)
    req = (ctypes.c_uint * 1)(1)                  # kWriteTo
    grad_h = (ctypes.c_void_p * 1)(g)
    ok(lib.MXAutogradMarkVariables(1, var_h, req, grad_h))

    n_out = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    ins = (ctypes.c_void_p * 2)(x, x)
    ok(lib.MXImperativeInvokeByName(b"_mul", 2, ins, ctypes.byref(n_out),
                                    ctypes.byref(outs), 0, None, None))
    heads = (ctypes.c_void_p * 1)(outs[0])
    ok(lib.MXAutogradComputeGradient(1, heads))
    ok(lib.MXAutogradSetIsTraining(0, ctypes.byref(prev)))
    gv = np.zeros(4, "f")
    ok(lib.MXNDArraySyncCopyToCPU(
        g, gv.ctypes.data_as(ctypes.c_void_p), gv.size))
    np.testing.assert_allclose(gv, 2 * xs, rtol=1e-5)
    lib.MXNDArrayFree(x)
    lib.MXNDArrayFree(g)

    # --- profiler: config -> run -> stop -> dump produces Chrome JSON
    import json
    fname = str(tmp_path / "prof.json")
    ok(lib.MXSetProfilerConfig(1, fname.encode()))
    ok(lib.MXSetProfilerState(1))
    a = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(a)))
    ins1 = (ctypes.c_void_p * 1)(a)
    ok(lib.MXImperativeInvokeByName(b"sqrt", 1, ins1, ctypes.byref(n_out),
                                    ctypes.byref(outs), 0, None, None))
    ok(lib.MXSetProfilerState(0))
    ok(lib.MXDumpProfile())
    events = json.load(open(fname))["traceEvents"]
    assert events, "profiler dump is empty"
    lib.MXNDArrayFree(a)


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_cpp_train_lenet_through_c_abi(tmp_path):
    """The C ABI's training story end-to-end: a C++ program (no Python)
    composes LeNet, feeds MNISTIter, runs forward/backward and SGD, and
    must LEARN (the reference cpp-package lenet.cpp contract)."""
    import shutil
    import subprocess
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cpp = os.path.join(root, "cpp-package")
    subprocess.run(["make", "-C", cpp, "train_lenet"], check=True,
                   capture_output=True, timeout=120)
    img, lbl = _write_synth_mnist(tmp_path, n=200)
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [os.path.join(cpp, "train_lenet"), img, lbl, "6", "0.9"],
        env=env, capture_output=True, text=True, timeout=240, cwd=root)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "train lenet OK" in res.stdout


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_c_function_api_and_monitor_callback(tmp_path):
    """Legacy Function API (MXListFunctions/MXFuncDescribe/MXFuncInvoke,
    c_api.h:166-260) + the executor monitor C callback
    (MXExecutorSetMonitorCallback, c_api.h:1049-1053)."""
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p

    def ok(rc):
        assert rc == 0, lib.MXGetLastError()

    # --- function listing + invoke: sqrt through the legacy API
    n = ctypes.c_uint()
    funcs = ctypes.POINTER(ctypes.c_void_p)()
    ok(lib.MXListFunctions(ctypes.byref(n), ctypes.byref(funcs)))
    sqrt_h = None
    name_p = ctypes.c_char_p()
    for i in range(n.value):
        ok(lib.MXFuncGetInfo(ctypes.c_void_p(funcs[i]),
                             ctypes.byref(name_p), None, None, None,
                             None, None))
        if name_p.value == b"sqrt":
            sqrt_h = ctypes.c_void_p(funcs[i])
    assert sqrt_h is not None and n.value > 200

    nu, ns, nm = ctypes.c_uint(), ctypes.c_uint(), ctypes.c_uint()
    mask = ctypes.c_int()
    ok(lib.MXFuncDescribe(sqrt_h, ctypes.byref(nu), ctypes.byref(ns),
                          ctypes.byref(nm), ctypes.byref(mask)))
    assert (nu.value, ns.value, nm.value) == (1, 0, 1)

    shape = (ctypes.c_uint * 1)(4)
    a = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(a)))
    xs = np.array([1.0, 4.0, 9.0, 16.0], "f")
    ok(lib.MXNDArraySyncCopyFromCPU(
        a, xs.ctypes.data_as(ctypes.c_void_p), xs.size))
    out = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(out)))
    use = (ctypes.c_void_p * 1)(a)
    mut = (ctypes.c_void_p * 1)(out)
    ok(lib.MXFuncInvoke(sqrt_h, use, None, mut))
    got = np.zeros(4, "f")
    ok(lib.MXNDArraySyncCopyToCPU(
        out, got.ctypes.data_as(ctypes.c_void_p), got.size))
    np.testing.assert_allclose(got, [1, 2, 3, 4], rtol=1e-6)

    # --- executor monitor C callback
    data = ctypes.c_void_p()
    ok(lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)))
    creators = ctypes.POINTER(ctypes.c_void_p)()
    ok(lib.MXSymbolListAtomicSymbolCreators(ctypes.byref(n),
                                            ctypes.byref(creators)))
    fc_creator = None
    for i in range(n.value):
        ok(lib.MXSymbolGetAtomicSymbolName(ctypes.c_void_p(creators[i]),
                                           ctypes.byref(name_p)))
        if name_p.value == b"FullyConnected":
            fc_creator = ctypes.c_void_p(creators[i])
    assert fc_creator is not None
    fc = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"3")
    ok(lib.MXSymbolCreateAtomicSymbol(fc_creator, 1, keys, vals,
                                      ctypes.byref(fc)))
    arg_keys = (ctypes.c_char_p * 1)(b"data")
    arg_vals = (ctypes.c_void_p * 1)(data)
    ok(lib.MXSymbolCompose(fc, b"fc", 1, arg_keys, arg_vals))
    exec_h = ctypes.c_void_p()
    in_keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape_data = (ctypes.c_uint * 2)(2, 4)
    ok(lib.MXExecutorSimpleBind(fc, 1, 0, 1, in_keys, indptr, shape_data,
                                b"write", ctypes.byref(exec_h)))

    seen = []
    CB = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                          ctypes.c_void_p)

    def on_tensor(tensor_name, nd_handle, _ctx):
        seen.append(tensor_name.decode())
        # contract: callee releases (wrap in c_void_p — a bare int would
        # marshal as 32-bit c_int and truncate the pointer)
        lib.MXNDArrayFree(ctypes.c_void_p(nd_handle))

    cb = CB(on_tensor)
    ok(lib.MXExecutorSetMonitorCallback(exec_h, cb, None))
    ok(lib.MXExecutorForward(exec_h, 1))
    assert any("fc" in s for s in seen), seen

    lib.MXExecutorFree(exec_h)
    lib.MXSymbolFree(fc)
    lib.MXSymbolFree(data)
    lib.MXNDArrayFree(a)
    lib.MXNDArrayFree(out)


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_c_ndarray_views_and_meta():
    """MXNDArraySlice/At/Reshape/GetDType/GetContext
    (reference c_api.h:330-405)."""
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p

    def ok(rc):
        assert rc == 0, lib.MXGetLastError()

    shape = (ctypes.c_uint * 2)(4, 3)
    a = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(a)))
    xs = np.arange(12, dtype="f").reshape(4, 3)
    ok(lib.MXNDArraySyncCopyFromCPU(
        a, xs.ctypes.data_as(ctypes.c_void_p), xs.size))

    def read(h, n):
        out = np.zeros(n, "f")
        ok(lib.MXNDArraySyncCopyToCPU(
            h, out.ctypes.data_as(ctypes.c_void_p), out.size))
        return out

    s = ctypes.c_void_p()
    ok(lib.MXNDArraySlice(a, 1, 3, ctypes.byref(s)))
    np.testing.assert_allclose(read(s, 6), xs[1:3].reshape(-1))

    at = ctypes.c_void_p()
    ok(lib.MXNDArrayAt(a, 2, ctypes.byref(at)))
    np.testing.assert_allclose(read(at, 3), xs[2])

    r = ctypes.c_void_p()
    dims = (ctypes.c_int * 2)(6, 2)
    ok(lib.MXNDArrayReshape(a, 2, dims, ctypes.byref(r)))
    np.testing.assert_allclose(read(r, 12), xs.reshape(-1))

    dt = ctypes.c_int()
    ok(lib.MXNDArrayGetDType(a, ctypes.byref(dt)))
    assert dt.value == 0                    # float32

    devt, devid = ctypes.c_int(), ctypes.c_int()
    ok(lib.MXNDArrayGetContext(a, ctypes.byref(devt), ctypes.byref(devid)))
    assert devt.value in (1, 6) and devid.value == 0

    for h in (s, at, r, a):
        lib.MXNDArrayFree(h)


@pytest.mark.skipif(not os.path.exists(_LIB),
                    reason="libmxtpu_c_api.so not built")
def test_c_misc_raw_bytes_seed_print():
    """MXNDArraySaveRawBytes/LoadFromRawBytes round-trip, MXRandomSeed,
    MXExecutorPrint."""
    lib = ctypes.CDLL(_LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p

    def ok(rc):
        assert rc == 0, lib.MXGetLastError()

    ok(lib.MXRandomSeed(42))

    shape = (ctypes.c_uint * 2)(2, 3)
    a = ctypes.c_void_p()
    ok(lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(a)))
    xs = np.arange(6, dtype="f").reshape(2, 3)
    ok(lib.MXNDArraySyncCopyFromCPU(
        a, xs.ctypes.data_as(ctypes.c_void_p), xs.size))
    size = ctypes.c_size_t()
    buf = ctypes.c_void_p()
    ok(lib.MXNDArraySaveRawBytes(a, ctypes.byref(size), ctypes.byref(buf)))
    raw = ctypes.string_at(buf.value, size.value)
    b = ctypes.c_void_p()
    ok(lib.MXNDArrayLoadFromRawBytes(raw, len(raw), ctypes.byref(b)))
    got = np.zeros((2, 3), "f")
    ok(lib.MXNDArraySyncCopyToCPU(
        b, got.ctypes.data_as(ctypes.c_void_p), got.size))
    np.testing.assert_allclose(got, xs)

    # executor print: bind a trivial graph, dump its debug string
    data = ctypes.c_void_p()
    ok(lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)))
    n = ctypes.c_uint()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    ok(lib.MXSymbolListAtomicSymbolCreators(ctypes.byref(n),
                                            ctypes.byref(creators)))
    name_p = ctypes.c_char_p()
    fc_creator = None
    for i in range(n.value):
        ok(lib.MXSymbolGetAtomicSymbolName(ctypes.c_void_p(creators[i]),
                                           ctypes.byref(name_p)))
        if name_p.value == b"FullyConnected":
            fc_creator = ctypes.c_void_p(creators[i])
    assert fc_creator is not None
    fc = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"3")
    ok(lib.MXSymbolCreateAtomicSymbol(fc_creator, 1, keys, vals,
                                      ctypes.byref(fc)))
    arg_keys = (ctypes.c_char_p * 1)(b"data")
    arg_vals = (ctypes.c_void_p * 1)(data)
    ok(lib.MXSymbolCompose(fc, b"fc", 1, arg_keys, arg_vals))
    exec_h = ctypes.c_void_p()
    in_keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape_data = (ctypes.c_uint * 2)(2, 4)
    ok(lib.MXExecutorSimpleBind(fc, 1, 0, 1, in_keys, indptr, shape_data,
                                b"write", ctypes.byref(exec_h)))
    s = ctypes.c_char_p()
    ok(lib.MXExecutorPrint(exec_h, ctypes.byref(s)))
    assert b"fc" in s.value

    lib.MXExecutorFree(exec_h)
    lib.MXSymbolFree(fc)
    lib.MXSymbolFree(data)
    lib.MXNDArrayFree(a)
    lib.MXNDArrayFree(b)
