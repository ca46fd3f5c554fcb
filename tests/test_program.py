"""The unified compiled-program artifact + persisted program cache
(``mxnet_tpu/program.py``, docs/how_to/compiled_programs.md).

Covers the cache-key invalidation matrix the safety story rests on —
flipped symbol digest, dtype policy, mesh/partition spec, a mocked
jax-version/platform change, and a byte-truncated entry must each MISS
cleanly and recompile (no crash, no wrong-program execution) — plus the
``program-bypass`` lint rule and the subprocess acceptance: a second
process reusing one cache dir compiles ZERO programs for the same
(symbol, shapes, policy, mesh) on the trainer, Predictor, and
ModelServer paths.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import program

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "progcache")
    monkeypatch.setenv("MXTPU_PROGRAM_CACHE", d)
    program.reset_stats()
    yield d
    program.reset_stats()


def _mm(x, y):
    return x @ y + 1.0


def _args():
    return jnp.ones((4, 8)), jnp.ones((8, 2))


# ----------------------------------------------------------------------
# core artifact behavior
def test_persist_and_load_roundtrip(cache_dir):
    p1 = program.CompiledProgram("t.mm", _mm, key={"id": "a"})
    out1 = p1(*_args())
    c = p1.counts()
    assert c["traces"] == 1 and c["disk_misses"] == 1
    assert len(os.listdir(cache_dir)) == 1
    # fresh program object, same key: loads, never traces
    p2 = program.CompiledProgram("t.mm", _mm, key={"id": "a"})
    out2 = p2(*_args())
    c2 = p2.counts()
    assert c2["traces"] == 0 and c2["disk_loads"] == 1
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_aot_statuses(cache_dir):
    sds = (jax.ShapeDtypeStruct((4, 8), jnp.float32),
           jax.ShapeDtypeStruct((8, 2), jnp.float32))
    p1 = program.CompiledProgram("t.mm", _mm, key={"id": "s"})
    assert p1.aot(*sds) == "compiled"
    assert p1.aot(*sds) == "cached"
    p2 = program.CompiledProgram("t.mm", _mm, key={"id": "s"})
    assert p2.aot(*sds) == "loaded"
    assert p2.loaded_from_disk(*_args())
    out = p2(*_args())
    assert p2.counts()["traces"] == 0
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(_mm(*_args())))


def test_no_disk_without_env(tmp_path, monkeypatch):
    monkeypatch.delenv("MXTPU_PROGRAM_CACHE", raising=False)
    p = program.CompiledProgram("t.mm", _mm, key={"id": "x"})
    p(*_args())
    assert p.counts()["traces"] == 1 and p.counts()["disk_misses"] == 0


def test_keyless_program_never_persists(cache_dir):
    p = program.jit("t.anon", _mm)
    p(*_args())
    assert not os.path.exists(cache_dir) or os.listdir(cache_dir) == []


# ----------------------------------------------------------------------
# invalidation matrix: every mismatch is a clean MISS + recompile
def test_flipped_symbol_digest_misses(cache_dir):
    p1 = program.CompiledProgram("t.mm", _mm, key={"symbol": "aaaa"})
    p1(*_args())
    p2 = program.CompiledProgram("t.mm", _mm, key={"symbol": "bbbb"})
    out = p2(*_args())
    c = p2.counts()
    assert c["disk_loads"] == 0 and c["traces"] == 1
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(_mm(*_args())))
    assert len(os.listdir(cache_dir)) == 2


def test_dtype_policy_misses(cache_dir):
    base = {"symbol": "s", "dtype_policy": None}
    p1 = program.CompiledProgram("t.mm", _mm, key=base)
    p1(*_args())
    p2 = program.CompiledProgram(
        "t.mm", _mm, key=dict(base, dtype_policy="legacy"))
    p2(*_args())
    assert p2.counts()["disk_loads"] == 0 and p2.counts()["traces"] == 1


def test_partition_spec_misses(cache_dir):
    """Same key, different input sharding (the mesh/partition-spec
    axis of the signature): a resharded input is a different program,
    never a false hit."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    mesh = Mesh(np.array(devs[:2]), ("data",))
    row = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    p1 = program.CompiledProgram("t.mm", _mm, key={"id": "mesh"})
    x, y = _args()
    p1(jax.device_put(x, row), jax.device_put(y, rep))
    assert p1.counts()["traces"] == 1
    # second process object, same key, same shapes, DIFFERENT spec
    p2 = program.CompiledProgram("t.mm", _mm, key={"id": "mesh"})
    out = p2(jax.device_put(x, rep), jax.device_put(y, rep))
    assert p2.counts()["disk_loads"] == 0 and p2.counts()["traces"] == 1
    np.testing.assert_allclose(np.asarray(out), np.asarray(_mm(x, y)))
    # and the matching spec DOES load
    p3 = program.CompiledProgram("t.mm", _mm, key={"id": "mesh"})
    p3(jax.device_put(x, row), jax.device_put(y, rep))
    assert p3.counts()["disk_loads"] == 1 and p3.counts()["traces"] == 0


def test_jax_version_change_misses(cache_dir, monkeypatch):
    p1 = program.CompiledProgram("t.mm", _mm, key={"id": "v"})
    p1(*_args())
    [entry] = os.listdir(cache_dir)
    monkeypatch.setattr(program, "_jax_version", lambda: "9.9.9/mock")
    p2 = program.CompiledProgram("t.mm", _mm, key={"id": "v"})
    sig = p2._call_sig(_args())
    # rename the old entry onto the NEW expected name: the file is
    # found but its recorded identity names the other jax — the
    # ident check must refuse it as STALE, not execute it
    os.rename(os.path.join(cache_dir, entry),
              os.path.join(cache_dir, p2._entry_key(sig) + ".mxprog"))
    stale_before = program.cache_stats()["cache_stale"]
    out = p2(*_args())
    assert p2.counts()["disk_loads"] == 0 and p2.counts()["traces"] == 1
    assert program.cache_stats()["cache_stale"] == stale_before + 1
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(_mm(*_args())))


def test_platform_change_misses(cache_dir, monkeypatch):
    p1 = program.CompiledProgram("t.mm", _mm, key={"id": "p"})
    p1(*_args())
    monkeypatch.setattr(program, "_backend", lambda: "tpu-mock")
    p2 = program.CompiledProgram("t.mm", _mm, key={"id": "p"})
    p2(*_args())
    assert p2.counts()["disk_loads"] == 0 and p2.counts()["traces"] == 1


def test_truncated_entry_is_stale_miss(cache_dir):
    p1 = program.CompiledProgram("t.mm", _mm, key={"id": "trunc"})
    out1 = p1(*_args())
    [entry] = os.listdir(cache_dir)
    with open(os.path.join(cache_dir, entry), "r+b") as f:
        f.truncate(17)
    stale_before = program.cache_stats()["cache_stale"]
    p2 = program.CompiledProgram("t.mm", _mm, key={"id": "trunc"})
    out2 = p2(*_args())          # no crash: recompiles
    assert p2.counts()["traces"] == 1
    assert program.cache_stats()["cache_stale"] == stale_before + 1
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # the recompile re-persisted a good entry
    p3 = program.CompiledProgram("t.mm", _mm, key={"id": "trunc"})
    p3(*_args())
    assert p3.counts()["disk_loads"] == 1


# ----------------------------------------------------------------------
# consumer integration
def _mlp():
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.symbol.SoftmaxOutput(net, name="softmax")


def test_compiled_forward_loads_across_cache_clear(cache_dir):
    from mxnet_tpu import serving
    from mxnet_tpu.serving.compiled import compiled_forward
    sym = _mlp()
    rng = np.random.RandomState(0)
    params = {"fc1_weight": jnp.asarray(rng.randn(16, 8).astype("f")),
              "fc1_bias": jnp.zeros(16, jnp.float32),
              "fc2_weight": jnp.asarray(rng.randn(4, 16).astype("f")),
              "fc2_bias": jnp.zeros(4, jnp.float32)}
    shapes = {"data": (4, 8), "softmax_label": (4,)}
    cf = compiled_forward(sym, ["data", "softmax_label"])
    assert cf.aot_compile(params, {}, shapes) == "compiled"
    feed = {"data": rng.randn(4, 8).astype("f"),
            "softmax_label": np.zeros(4, "f")}
    out1 = np.asarray(cf.run(params, {}, feed)[0])
    # a fresh process is simulated by clearing the in-memory keyed
    # cache: the rebuilt CompiledForward must deserialize, not compile
    serving.clear_cache()
    cf2 = compiled_forward(sym, ["data", "softmax_label"])
    assert cf2 is not cf
    assert cf2.aot_compile(params, {}, shapes) == "loaded"
    out2 = np.asarray(cf2.run(params, {}, feed)[0])
    assert cf2.counts()["traces"] == 0
    np.testing.assert_array_equal(out1, out2)


def test_trainer_key_separates_configs(cache_dir):
    """Two trainers differing only in dtype_policy write DISTINCT
    entries — the config axis of the invalidation matrix on the real
    trainer path."""
    def build(policy):
        t = mx.parallel.Trainer(
            _mlp(), mx.optimizer.create("sgd", learning_rate=0.1),
            dtype_policy=policy)
        t.bind(data_shapes={"data": (4, 8)},
               label_shapes={"softmax_label": (4,)})
        t.init_params(mx.init.Xavier())
        return t
    rng = np.random.RandomState(0)
    batch = {"data": mx.nd.array(rng.randn(4, 8).astype("f")),
             "softmax_label": mx.nd.array(
                 rng.randint(0, 4, 4).astype("f"))}
    build("bytediet").step(batch)
    n1 = len(os.listdir(cache_dir))
    build("legacy").step(batch)
    n2 = len(os.listdir(cache_dir))
    assert n2 > n1, "legacy-policy step must not reuse bytediet entries"


def test_executor_eval_forward_persists(cache_dir):
    sym = _mlp()
    exe = sym.simple_bind(grad_req="null", data=(4, 8),
                          softmax_label=(4,))
    rng = np.random.RandomState(1)
    exe.forward(is_train=False, data=mx.nd.array(
        rng.randn(4, 8).astype("f")))
    assert len(os.listdir(cache_dir)) >= 1


# ----------------------------------------------------------------------
# program-bypass lint
def test_program_bypass_rule(tmp_path):
    from mxnet_tpu.analysis import scan_program_bypass
    d = tmp_path / "pkg"
    (d / "serving").mkdir(parents=True)
    (d / "serving" / "bad.py").write_text(
        "import jax\n"
        "def build(fn, args):\n"
        "    j = jax.jit(fn)\n"
        "    c = j.lower(*args).compile()\n"
        "    ok = jax.jit(fn)  # program: ok bench-only probe\n"
        "    return c\n")
    findings = scan_program_bypass(str(d))
    assert [f.rule for f in findings] == ["program-bypass"] * 2
    assert findings[0].severity == "warn"
    assert "build" in findings[0].layer
    assert {f.op for f in findings} == {"jax.jit", "lower().compile()"}


def test_program_bypass_head_clean():
    """The shipped trainer/executor/serving layers route every compile
    through CompiledProgram (the LINT_BASELINE gate at zero)."""
    from mxnet_tpu.analysis import lint_program_source
    report = lint_program_source()
    assert report.counts() == {"error": 0, "warn": 0, "info": 0}, [
        f.format() for f in report.findings]


# ----------------------------------------------------------------------
# acceptance: a second PROCESS compiles zero programs on all three paths
def test_second_process_compiles_nothing(tmp_path):
    """tests/nightly/program_warm.py drives trainer + Predictor +
    ModelServer against one cache dir; the second process must load
    every executable (compiles == 0, traces == 0) and reproduce the
    first run's output fingerprints bit-for-bit."""
    cache = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_PROGRAM_CACHE=cache)
    env.pop("XLA_FLAGS", None)   # one CPU device, like a real restart
    script = os.path.join(ROOT, "tests", "nightly", "program_warm.py")

    def run(expect):
        r = subprocess.run([sys.executable, script, "--expect", expect],
                           env=env, capture_output=True, text=True,
                           timeout=240)
        assert r.returncode == 0, r.stdout + r.stderr
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("PROGRAM_WARM ")][-1]
        return json.loads(line[len("PROGRAM_WARM "):])

    cold = run("cold")
    assert cold["compiles"] > 0 and cold["persists"] > 0
    warm = run("warm")
    assert warm["compiles"] == 0 and warm["traces"] == 0
    assert warm["loads"] == cold["persists"]
    assert warm["warmup_loaded"] > 0      # server skipped its warmups
    assert warm["fingerprints"] == cold["fingerprints"]


# ----------------------------------------------------------------------
# the compile cache that can be placed from outside
@pytest.mark.parametrize("placed", ["/some/where/else", None])
def test_place_compile_cache(monkeypatch, placed):
    """JAX_COMPILATION_CACHE_DIR set: JAX's cache is there and the code
    sets no other.  Unset: <checkout>/.jax_cache, never a temporary
    name.  The program cache, when armed, is a fixed directory under
    the checkout."""
    import jax
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    local = os.path.join(root, ".jax_cache")
    # set, then deleted: monkeypatch then has what was there before the
    # test to put back.  A bare delenv of an absent name records nothing,
    # and the setenv below would then record, and restore into every later
    # test of this worker, what place_compile_cache itself wrote
    monkeypatch.setenv("MXTPU_PROGRAM_CACHE", "")
    monkeypatch.delenv("MXTPU_PROGRAM_CACHE")
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert program.place_compile_cache() == (placed or local)
        assert "MXTPU_PROGRAM_CACHE" not in os.environ
        assert jax.config.jax_compilation_cache_dir == \
            (before if placed else local)
        assert program.place_compile_cache(programs=True) == \
            (placed or local)
        assert program.cache_dir() == os.path.join(local, "programs")
        # a program cache armed from outside stays where it was put
        monkeypatch.setenv("MXTPU_PROGRAM_CACHE", "/armed/outside")
        program.place_compile_cache(programs=True)
        assert program.cache_dir() == "/armed/outside"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        os.environ.pop("MXTPU_PROGRAM_CACHE", None)
