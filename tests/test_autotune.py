"""Search-based autotuning: plan persistence/compat through Trainer and
ModelServer, the central env-knob registry, seedable arrival schedules,
the importable cost model, and the micro-tune acceptance drill
(docs/how_to/autotune.md)."""
import json
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu import envknobs, program, serving, tuneplan  # noqa: E402
from mxnet_tpu import obs as _obs                         # noqa: E402
from mxnet_tpu.base import MXNetError                    # noqa: E402
from mxnet_tpu.parallel.trainer import Trainer           # noqa: E402


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=8, name="fc2")
    return mx.symbol.SoftmaxOutput(net, name="softmax")


def _sgd(batch=8):
    return mx.optimizer.create("sgd", learning_rate=0.1,
                               rescale_grad=1.0 / batch)


def _plan_for(sym=None, train=None, serve=None, **key_over):
    key = tuneplan.current_key(
        symbol_digest=program.symbol_digest(sym) if sym is not None
        else None)
    key.update(key_over)
    return {"version": tuneplan.PLAN_VERSION, "key": key,
            "train": train or {}, "serve": serve or {},
            "measured": {}, "meta": {}}


def _clean_env(monkeypatch):
    for name in ("MXTPU_TUNE_PLAN", "MXTPU_GRAD_ACCUM", "MXTPU_ZERO",
                 "MXTPU_SERVE_MAX_WAIT_US", "MXTPU_SERVE_BUCKETS",
                 "MXTPU_SERVE_QUEUE_CAP", "MXTPU_SERVE_SHED_POLICY",
                 "MXTPU_REMAT", "MXTPU_DTYPE_POLICY"):
        monkeypatch.delenv(name, raising=False)


# ----------------------------------------------------------------------
class TestPlanResolution:
    def test_trainer_roundtrip_dict_and_path(self, tmp_path,
                                             monkeypatch):
        _clean_env(monkeypatch)
        sym = _mlp()
        plan = _plan_for(sym, train={"grad_accum": 2, "remat": "none",
                                     "zero": 0})
        t = Trainer(sym, _sgd(), plan=plan)
        assert t.grad_accum == 2
        assert t.plan_knobs == plan["train"]
        # the persisted round trip: save -> path -> Trainer
        p = str(tmp_path / "plan.json")
        tuneplan.save(p, plan)
        t2 = Trainer(sym, _sgd(), plan=p)
        assert t2.grad_accum == 2

    def test_env_overrides_plan_entry(self, monkeypatch):
        _clean_env(monkeypatch)
        sym = _mlp()
        plan = _plan_for(sym, train={"grad_accum": 2})
        monkeypatch.setenv("MXTPU_GRAD_ACCUM", "3")
        t = Trainer(sym, _sgd(), plan=plan)
        assert t.grad_accum == 3          # env beats plan

    def test_ctor_overrides_env_and_plan(self, monkeypatch):
        _clean_env(monkeypatch)
        sym = _mlp()
        plan = _plan_for(sym, train={"grad_accum": 2})
        monkeypatch.setenv("MXTPU_GRAD_ACCUM", "3")
        t = Trainer(sym, _sgd(), plan=plan, grad_accum=4)
        assert t.grad_accum == 4          # ctor beats everything

    def test_foreign_symbol_falls_back_counted(self, monkeypatch):
        _clean_env(monkeypatch)
        sym = _mlp()
        plan = _plan_for(sym, train={"grad_accum": 2})
        plan["key"]["symbol"] = "deadbeef" * 5
        before = int(_obs.counter("tune.plan_foreign").value)
        t = Trainer(sym, _sgd(), plan=plan)
        assert t.grad_accum == 1          # default, not the plan value
        assert t.plan_knobs == {}
        assert int(_obs.counter("tune.plan_foreign").value) == before + 1

    def test_foreign_mesh_falls_back(self, monkeypatch):
        _clean_env(monkeypatch)
        sym = _mlp()
        plan = _plan_for(sym, train={"grad_accum": 2})
        plan["key"]["mesh"] = {"axes": {"data": 2}, "devices": 2}
        t = Trainer(sym, _sgd(), plan=plan)   # meshless trainer
        assert t.grad_accum == 1

    def test_meshless_key_rejected_on_a_real_mesh(self, monkeypatch):
        # a tool-emitted plan stamps the MEASURED identity ({"axes": {},
        # "devices": 1}); it must not silently configure a meshed
        # trainer (null stays the hand-written wildcard)
        _clean_env(monkeypatch)
        import jax
        from mxnet_tpu import parallel
        devices = jax.devices()
        if len(devices) < 2:
            pytest.skip("needs >= 2 devices")
        sym = _mlp()
        plan = _plan_for(sym, train={"grad_accum": 2})
        plan["key"]["mesh"] = dict(tuneplan.MESHLESS)
        mesh = parallel.make_mesh({"data": 2}, devices[:2])
        before = int(_obs.counter("tune.plan_foreign").value)
        t = Trainer(sym, _sgd(), plan=plan, mesh=mesh)
        assert t.grad_accum == 1          # foreign: measured meshless
        assert int(_obs.counter("tune.plan_foreign").value) == before + 1
        # and the meshless consumer still matches the meshless key
        t2 = Trainer(sym, _sgd(), plan=plan)
        assert t2.grad_accum == 2

    def test_wildcard_key_fields_match(self, monkeypatch):
        _clean_env(monkeypatch)
        sym = _mlp()
        plan = _plan_for(None, train={"grad_accum": 2})
        assert plan["key"]["symbol"] is None      # wildcard
        plan["key"]["jax"] = None
        t = Trainer(sym, _sgd(), plan=plan)
        assert t.grad_accum == 2

    def test_env_plan_path_applies(self, tmp_path, monkeypatch):
        _clean_env(monkeypatch)
        sym = _mlp()
        p = str(tmp_path / "plan.json")
        tuneplan.save(p, _plan_for(sym, train={"grad_accum": 2}))
        monkeypatch.setenv("MXTPU_TUNE_PLAN", p)
        t = Trainer(sym, _sgd())
        assert t.grad_accum == 2

    def test_env_plan_path_missing_is_loud(self, monkeypatch):
        _clean_env(monkeypatch)
        monkeypatch.setenv("MXTPU_TUNE_PLAN", "/nonexistent/plan.json")
        with pytest.raises(MXNetError, match="cannot read tune plan"):
            Trainer(_mlp(), _sgd())

    def test_server_roundtrip_and_env_override(self, monkeypatch):
        _clean_env(monkeypatch)
        serve = {"buckets": [1, 2, 8], "max_wait_us": 500,
                 "queue_cap": 9, "shed_policy": "block"}
        plan = _plan_for(None, serve=serve)
        s = serving.ModelServer(plan=plan)
        assert s.buckets == [1, 2, 8]
        assert s.max_wait_s == 500 / 1e6
        assert s.queue_cap == 9
        assert s.shed_policy == "block"
        assert s.plan_knobs == serve
        # a set env var beats the plan entry
        monkeypatch.setenv("MXTPU_SERVE_MAX_WAIT_US", "999")
        s2 = serving.ModelServer(plan=plan)
        assert s2.max_wait_s == 999 / 1e6
        assert s2.buckets == [1, 2, 8]    # untouched knobs still apply

    def test_server_foreign_mesh_falls_back(self, monkeypatch):
        _clean_env(monkeypatch)
        plan = _plan_for(None, serve={"max_wait_us": 500})
        plan["key"]["mesh"] = {"axes": {"data": 2}, "devices": 2}
        before = int(_obs.counter("tune.plan_foreign").value)
        s = serving.ModelServer(plan=plan)
        assert s.max_wait_s == 2000 / 1e6     # default
        assert int(_obs.counter("tune.plan_foreign").value) == before + 1

    def test_malformed_plan_is_loud(self, tmp_path):
        with pytest.raises(MXNetError, match="grad_accum"):
            tuneplan.validate(_plan_for(None, train={"grad_acum": 2}))
        with pytest.raises(MXNetError, match="version"):
            tuneplan.validate({"version": 99, "key": {}})
        with pytest.raises(MXNetError, match="buckets"):
            tuneplan.validate(_plan_for(None, serve={"buckets": []}))
        p = tmp_path / "broken.json"
        p.write_text("{not json")
        with pytest.raises(MXNetError, match="not valid JSON"):
            tuneplan.load(str(p))


# ----------------------------------------------------------------------
class TestEnvKnobs:
    def test_unknown_knob_warns_with_suggestion(self):
        with pytest.warns(envknobs.KnobWarning,
                          match="MXTPU_GRAD_ACCUM"):
            found = envknobs.validate_environ(
                {"MXTPU_GRAD_ACUM": "4"})
        assert found and found[0][0] == "MXTPU_GRAD_ACUM"

    def test_bad_typed_value_flagged(self):
        with pytest.warns(envknobs.KnobWarning,
                          match="not an integer"):
            found = envknobs.validate_environ({"MXTPU_ZERO": "abc"})
        assert found
        # list knobs warn too (a raw ValueError here used to abort
        # `import mxnet_tpu` outright)
        with pytest.warns(envknobs.KnobWarning, match="integer list"):
            found = envknobs.validate_environ(
                {"MXTPU_SERVE_BUCKETS": "1,a,8"})
        assert found

    def test_strict_mode_raises(self):
        with pytest.raises(MXNetError, match="MXTPU_GRAD_ACCUM"):
            envknobs.validate_environ({"MXTPU_GRAD_ACUM": "4"},
                                      strict=True)

    def test_clean_env_is_silent(self):
        assert envknobs.validate_environ(
            {"MXTPU_ZERO": "1", "PATH": "/bin"}) == []

    def test_typed_getters(self, monkeypatch):
        monkeypatch.setenv("MXTPU_SERVE_CAP", "17")
        assert envknobs.get_int("MXTPU_SERVE_CAP", 3) == 17
        monkeypatch.setenv("MXTPU_SERVE_CAP", "x")
        with pytest.raises(MXNetError, match="not an integer"):
            envknobs.get_int("MXTPU_SERVE_CAP", 3)
        monkeypatch.delenv("MXTPU_SERVE_CAP")
        assert envknobs.get_int("MXTPU_SERVE_CAP", 3) == 3

    def test_every_registered_knob_has_a_reader(self):
        """A name the registry holds occurs in some ``*.py`` or ``*.sh``
        of the tree besides the registry and the tests: a setting
        whose reader was deleted leaves with it."""
        import conftest
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        read = set()
        for path in conftest.repo_files():
            if path.endswith((".py", ".sh")) \
                    and not path.startswith("tests" + os.sep) \
                    and path != os.path.join("mxnet_tpu", "envknobs.py"):
                with open(os.path.join(root, path), errors="replace") as f:
                    read.update(re.findall(r"MXTPU_[A-Z0-9_]+", f.read()))
        assert sorted(set(envknobs.KNOBS) - read) == []


# ----------------------------------------------------------------------
class TestArrivalSchedule:
    def test_seeded_and_reusable(self):
        from tools.serve_bench import arrival_schedule
        a = arrival_schedule(50, 100.0, seed=7)
        b = arrival_schedule(50, 100.0, seed=7)
        assert np.array_equal(a, b)
        assert len(a) == 50 and np.all(np.diff(a) >= 0)
        # different seed, different draw
        assert not np.array_equal(a, arrival_schedule(50, 100.0, seed=8))

    def test_rate_rescales_same_sequence(self):
        # the same seed at any rate is the SAME unit-rate sequence,
        # rescaled — what makes cross-config comparisons arrival-fair
        from tools.serve_bench import arrival_schedule
        a = arrival_schedule(50, 100.0, seed=7)
        c = arrival_schedule(50, 200.0, seed=7)
        np.testing.assert_allclose(a, 2.0 * c, rtol=1e-12)


# ----------------------------------------------------------------------
class TestCostModel:
    def test_importable_surrogate(self):
        from tools.stepcost import cost_model
        out = cost_model({"model": "mlp", "batch": 8})
        assert out["gb_per_step"] > 0
        assert out["bytes"] > 0
        assert out["config"]["model"] == "mlp"

    def test_unknown_config_key_is_loud(self):
        from tools.stepcost import cost_model
        with pytest.raises(ValueError, match="grad_accum"):
            cost_model({"model": "mlp", "grad_acum": 2})


# ----------------------------------------------------------------------
class TestMicroTune:
    def test_micro_tune_acceptance(self, tmp_path, monkeypatch):
        """The end-to-end drill: the micro search emits a valid,
        loadable plan; every timed window appended a full
        (config, measured) corpus row; and a re-run of the winning
        timed trial against the warm program cache compiles ZERO
        programs (asserted via program.cache_stats deltas)."""
        _clean_env(monkeypatch)
        cache = str(tmp_path / "cache")
        monkeypatch.setenv("MXTPU_PROGRAM_CACHE", cache)
        out = str(tmp_path / "TUNE_PLAN.json")
        corpus = str(tmp_path / "TUNE_CORPUS.jsonl")
        from tools import autotune
        plan, summary = autotune.run_tune(
            micro=True, out=out, corpus=corpus, requests=150, seed=0)

        # plan: valid, loadable, keyed to this process
        loaded = tuneplan.load(out)
        assert loaded["serve"]["buckets"]
        assert loaded["key"]["symbol"]
        assert loaded["measured"]["warm_recheck_compiles"] == 0
        assert summary["plan_no_worse"] in (True, False)  # computed

        # corpus: one row per timed window, full config + measured
        rows = [json.loads(ln) for ln in open(corpus)]
        serve_rows = [r for r in rows if r["kind"] == "serve"]
        assert len(serve_rows) >= 6       # 3 trials x 2 windows
        for r in serve_rows:
            assert r["config"]["buckets"]
            assert "p50_ms" in r["measured"]
            assert "goodput_rps" in r["measured"]
            assert r["jax"] and r["platform"]

        # the plan round-trips through BOTH consumers
        from tools.serve_bench import build_model
        sym, wargs, waux, example = build_model("mlp", 0)
        t = Trainer(sym, _sgd(), plan=out)
        assert t.plan_knobs == loaded["train"]
        s = serving.ModelServer(plan=out)
        assert s.buckets == sorted(loaded["serve"]["buckets"])

        # the acceptance assertion proper: a REPEATED timed trial at
        # the winning config against the now-warm cache compiles 0
        # new programs (loads only)
        from tools.serve_bench import (_mixed_payloads,
                                       arrival_schedule)
        payloads = _mixed_payloads(example, (1, 2, 4), 60, 2)
        arrivals = arrival_schedule(60, 200.0, 3)
        with program.stats_delta() as d:
            m = autotune.timed_serve_trial(
                sym, wargs, waux, example, loaded["serve"], payloads,
                arrivals, 200.0, 250, corpus=corpus,
                label="test:warm", windows=1)
        assert d["compiles"] == 0, d
        assert m["program_compiles"] == 0
        assert m["program_loads"] > 0     # came off the disk cache
