"""Static memory analyzer (``mxnet_tpu/analysis/mem_passes.py``):
buffer-liveness peak prediction with layer provenance, exact per-chip
pricing of ZeRO-sharded state, the remat A/B ordering property
(checkpointing must LOWER the predicted peak), one crafted fixture per
mem rule (positive + clean), scan-carried state exempt from
``donation-missed`` (the grad-accum path), memory-aware serving
admission + pad-occupancy counters, autotune's capacity pruning, and
the HEAD zero-error sweep via the ``tools/mem_lint.py --check`` gate."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import parallel, serving
from mxnet_tpu.analysis import mem_passes
from mxnet_tpu.base import MXNetError

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=240, **kw):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, cwd=_ROOT, timeout=timeout, **kw)


def _find(report, rule, severity=None):
    return [f for f in report.findings if f.rule == rule
            and (severity is None or f.severity == severity)]


def _mlp_trainer(zero=1, grad_dtype="bf16", n=2):
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=512, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    mesh = parallel.make_mesh({"data": n}, jax.devices()[:n])
    t = parallel.Trainer(
        sym, mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9),
        mesh=mesh, zero=zero, grad_dtype=grad_dtype)
    t.bind(data_shapes={"data": (8, 600)},
           label_shapes={"softmax_label": (8,)})
    t.init_params(mx.init.Xavier())
    return t


def _tfm_trainer(remat):
    """A 2-layer transformer LM — enough attention/MLP residuals that
    the remat knob has real bytes to reclaim."""
    from mxnet_tpu import models
    sym = models.get_symbol("transformer", num_classes=16, seq_len=32,
                            num_hidden=64, num_heads=4, num_layers=2)
    mesh = parallel.make_mesh({"data": 2}, jax.devices()[:2])
    t = parallel.Trainer(sym, mx.optimizer.create("sgd",
                                                  learning_rate=0.1),
                         mesh=mesh, remat=remat)
    t.bind(data_shapes={"data": (4, 32)},
           label_shapes={"softmax_label": (4, 32)})
    t.init_params(mx.init.Xavier())
    return t


# ======================================================================
# the liveness timeline
def test_trainer_timeline_peak_with_provenance():
    """The fused step's timeline: a real peak, an argmax program
    point with a symbol-layer attribution, and per-layer live bytes."""
    t = _mlp_trainer()
    tl = t.mem_timeline()
    assert tl.peak_bytes_per_chip > 0
    assert tl.n_points > 0 and 0 <= tl.peak_index < tl.n_points
    assert tl.peak_point != "<empty>"
    assert tl.peak_layers and tl.peak_buffers
    # the top contributor at the peak is a real buffer with a layer
    top = tl.top_contributors(1)[0]
    assert top["bytes"] > 0 and top["desc"]
    # deterministic re-walk
    assert t.mem_timeline().peak_bytes_per_chip == tl.peak_bytes_per_chip
    assert t.predicted_peak_bytes() == tl.peak_bytes_per_chip


def test_zero1_prices_opt_state_per_chip():
    """ZeRO-sharded optimizer state enters the timeline at its
    committed per-chip size — EXACTLY ``opt_state_bytes_per_chip``, for
    both the sharded and the replicated corner (so the agreement is the
    sharding plan's, not a coincidence of the heuristic)."""
    peaks = {}
    for zero in (0, 1):
        t = _mlp_trainer(zero=zero)
        tl = t.mem_timeline()
        assert tl.input_bytes["opt_state"] == t.opt_state_bytes_per_chip()
        peaks[zero] = tl
    # the sharded corner holds strictly less state per chip
    assert peaks[1].input_bytes["opt_state"] < \
        peaks[0].input_bytes["opt_state"]


def test_remat_ab_ordering_property():
    """The knob's reason to exist, as a predicted-peak ordering:
    remat=none > remat=dots > remat=nothing on a transformer step
    (checkpointed regions are priced at their transient working-set
    floor, not at cumulative recompute liveness)."""
    peak = {r: _tfm_trainer(r).predicted_peak_bytes()
            for r in ("none", "dots", "nothing")}
    assert peak["none"] > peak["dots"] > peak["nothing"], peak


# ======================================================================
# rule fixtures: one positive + one clean case each
def test_mem_capacity_breach_and_fit():
    t = _mlp_trainer()
    tl = t.mem_timeline()
    rep = mem_passes.lint_mem(None, model="t", timeline=tl,
                              config={"capacity_bytes": 1})
    errs = _find(rep, "mem-capacity", "error")
    assert len(errs) == 1
    assert "OOMs before step 1" in errs[0].message
    # the error names the top contributors, not just the number
    assert "MB" in errs[0].message
    # clean: exactly fits
    rep = mem_passes.lint_mem(
        None, model="t", timeline=tl,
        config={"capacity_bytes": tl.peak_bytes_per_chip})
    assert not _find(rep, "mem-capacity")


def test_mem_budget_ratchet():
    t = _mlp_trainer()
    tl = t.mem_timeline()
    gb = mem_passes.timeline_peak_gb(tl)
    # regression past tolerance: error
    rep = mem_passes.lint_mem(None, model="t", timeline=tl,
                              config={"mem_baseline_gb": gb / 2,
                                      "mem_tolerance_pct": 5.0})
    errs = _find(rep, "mem-budget", "error")
    assert len(errs) == 1 and "regressed" in errs[0].message
    # within tolerance: silent
    rep = mem_passes.lint_mem(None, model="t", timeline=tl,
                              config={"mem_baseline_gb": gb * 1.01,
                                      "mem_tolerance_pct": 5.0})
    assert not _find(rep, "mem-budget")
    # improvement past tolerance: INFO nudge to ratchet down
    rep = mem_passes.lint_mem(None, model="t", timeline=tl,
                              config={"mem_baseline_gb": gb * 2,
                                      "mem_tolerance_pct": 5.0})
    infos = _find(rep, "mem-budget", "info")
    assert len(infos) == 1 and "ratchet" in infos[0].message


def test_remat_opportunity_fires_only_with_remat_off():
    t = _mlp_trainer()
    tl = t.mem_timeline()
    assert tl.residual_bytes > 0          # fwd residuals cross into bwd
    cfg = {"is_train": True, "remat": None, "remat_min_bytes": 1}
    rep = mem_passes.lint_mem(None, model="t", timeline=tl, config=cfg)
    warns = _find(rep, "remat-opportunity", "warn")
    assert len(warns) == 1 and "remat off" in warns[0].message
    # clean 1: remat is ON — nothing to suggest
    rep = mem_passes.lint_mem(
        None, model="t", timeline=tl,
        config={"is_train": True, "remat": "dots", "remat_min_bytes": 1})
    assert not _find(rep, "remat-opportunity")
    # clean 2: an eval program has no bwd to trade against
    rep = mem_passes.lint_mem(
        None, model="t", timeline=tl,
        config={"is_train": False, "remat": None, "remat_min_bytes": 1})
    assert not _find(rep, "remat-opportunity")


def test_donation_missed_fires_and_scan_carry_is_exempt():
    """A >=1 MB non-donated state leaf with a same-shaped output warns;
    the SAME leaf flowing through a ``lax.scan`` carry (the grad-accum
    microbatch loop) counts as donated — XLA aliases loop carries in
    place, so flagging it would be a false positive."""
    big = jax.ShapeDtypeStruct((512, 600), np.float32)      # 1.2 MB
    xs = jax.ShapeDtypeStruct((3, 512, 600), np.float32)
    cfg = {"donated_invars": [False, False],
           "invar_labels": ["opt_state['w']", "data"],
           "is_train": True}

    def plain_update(w, xs):
        return w + xs[0]

    rep = mem_passes.lint_mem(jax.make_jaxpr(plain_update)(big, xs),
                              model="crafted", config=dict(cfg))
    warns = _find(rep, "donation-missed", "warn")
    assert len(warns) == 1
    assert "opt_state['w']" in warns[0].message

    def scan_update(w, xs):
        def tick(c, x):
            return c + x, ()
        w, _ = lax.scan(tick, w, xs)
        return w

    rep = mem_passes.lint_mem(jax.make_jaxpr(scan_update)(big, xs),
                              model="crafted", config=dict(cfg))
    assert not _find(rep, "donation-missed")
    # clean: the leaf IS donated
    donated = dict(cfg, donated_invars=[True, False])
    rep = mem_passes.lint_mem(jax.make_jaxpr(plain_update)(big, xs),
                              model="crafted", config=donated)
    assert not _find(rep, "donation-missed")


def test_pad_waste_rule():
    occ = {4: {"rows_real": 1, "rows_padded": 4}}
    peaks = {4: 8 << 20}
    rep = mem_passes.lint_mem(
        None, model="srv",
        config={"pad_occupancy": occ, "bucket_peak_bytes": peaks,
                "pad_waste_min_bytes": 1})
    warns = _find(rep, "pad-waste", "warn")
    assert len(warns) == 1
    assert "tighten the bucket ladder" in warns[0].message
    # clean: every dispatched row was real
    rep = mem_passes.lint_mem(
        None, model="srv",
        config={"pad_occupancy": {4: {"rows_real": 4, "rows_padded": 4}},
                "bucket_peak_bytes": peaks, "pad_waste_min_bytes": 1})
    assert not _find(rep, "pad-waste")


# ======================================================================
# serving: admission ledger + pad occupancy counters
def _srv_mlp(nh=64, in_dim=32):
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=nh, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=8, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    shapes, _, _ = sym.infer_shape(data=(2, in_dim))
    rng = np.random.RandomState(0)
    args = {n: rng.randn(*s).astype("f") * 0.1
            for n, s in zip(sym.list_arguments(), shapes)
            if n != "data" and not n.endswith("label")}
    return sym, args, (in_dim,)


def test_serving_pad_counters_and_predicted_peak():
    serving.clear_cache()
    sym, args, example = _srv_mlp()
    srv = serving.ModelServer(buckets=[1, 4], max_wait_us=1000)
    srv.add_model("m", sym, args, {}, input_shapes={"data": example})
    m = srv._models["m"]
    # the per-tenant ledger demand: forward peak at the WORST bucket,
    # strictly above the resident weights it includes
    assert m.predicted_peak_bytes > m.weight_bytes_on_device > 0
    with srv:
        srv.predict(data=np.zeros((3,) + example, "f"))   # bucket 4
        st = srv.stats()
    pm = st["per_model"]["m"]
    assert pm["pad_rows"] == 1
    assert pm["pad_frac"] == 0.25
    assert pm["predicted_peak_bytes"] == m.predicted_peak_bytes
    assert st["policy"]["mem_budget_bytes"] == 0        # admission off


def test_serving_mem_budget_admission():
    serving.clear_cache()
    sym, args, example = _srv_mlp()
    # a 1 KB budget refuses the first tenant, loudly and by name
    srv = serving.ModelServer(buckets=[1, 4], mem_budget=1000)
    with pytest.raises(MXNetError) as err:
        srv.add_model("big", sym, args, {},
                      input_shapes={"data": example})
    msg = str(err.value)
    assert "refused" in msg and "serve memory budget" in msg
    assert "big" in msg
    assert "big" not in srv._models           # nothing half-admitted
    # a generous budget admits and the policy reports the ceiling
    srv2 = serving.ModelServer(buckets=[1, 4], mem_budget=1 << 30)
    srv2.add_model("m", sym, args, {}, input_shapes={"data": example})
    with srv2:
        st = srv2.stats()
    assert st["policy"]["mem_budget_bytes"] == 1 << 30
    assert st["per_model"]["m"]["predicted_peak_bytes"] > 0


# ======================================================================
# autotune: memory-feasibility pruning
def test_train_surrogate_capacity_prunes():
    """A capacity between the micro space's min and max predicted peaks
    marks >=1 config infeasible, sorts it LAST (never adopted, never
    timed), and every row still carries its predicted peak."""
    from tools.autotune import train_space, train_surrogate
    space = train_space(micro=True, devices=2)
    rows = train_surrogate(space, capacity=None)
    assert all(r["predicted_peak_bytes"] > 0 for r in rows)
    assert all(r["mem_feasible"] for r in rows)
    peaks = sorted(r["predicted_peak_bytes"] for r in rows)
    assert peaks[0] < peaks[-1], "micro space peaks must differ"
    cap = (peaks[0] + peaks[-1]) // 2
    rows2 = train_surrogate(space, capacity=cap)
    skipped = sum(1 for r in rows2 if not r["mem_feasible"])
    assert skipped >= 1
    assert rows2[0]["mem_feasible"]
    assert all(not r["mem_feasible"] for r in rows2[-skipped:])


# ======================================================================
# CLI gate
def test_cli_head_sweep_clean_and_gate_ok():
    """The zero-error sweep: every mem target at HEAD is clean, the
    checked-in MEM_BASELINE.json gate passes, and the timeline print
    carries layer provenance."""
    res = _run(["tools/mem_lint.py", "--check", "--json"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "baseline gate OK" in res.stdout
    start = res.stdout.index("{")
    end = res.stdout.rindex("}") + 1
    reports = json.loads(res.stdout[start:end])
    for target in ("trainer-step", "serving-forward", "ring-attention",
                   "pipeline"):
        assert reports[target]["counts"]["error"] == 0, target
    assert "mem-timeline[trainer-step]" in res.stdout
    assert "params" in res.stdout          # state priced, attributed


def test_cli_gate_fails_on_injected_capacity_breach():
    res = _run(["tools/mem_lint.py", "trainer-step", "--inject",
                "capacity", "--check"])
    assert res.returncode == 1, res.stdout + res.stderr
    assert "mem-capacity" in res.stdout
    assert "baseline gate FAILED" in res.stdout


def test_cli_mem_lint_live():
    """``tools/mem_lint.py --live``: the liveness top-10 view of the
    MLP trainer's fused step (trace-only)."""
    res = _run(["tools/mem_lint.py", "trainer-step", "--live"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "mem-timeline[trainer-step]: peak" in res.stdout
    assert "opt_state" in res.stdout
    top = res.stdout.split("mem-timeline[trainer-step]")[1] \
        .split("graph-lint[")[0]
    assert top.count(" MB  ") == 10, top


# ======================================================================
# the model against the compiler
# predicted/measured band for the liveness model.  The static model
# prices every UNFUSED intermediate, so it lands ABOVE what fusion
# materializes (1.45x on this trainer on the CPU) — the band is a drift
# alarm for the walker (a double-counted body reads >=2x, a dropped
# scope <0.5x), not a byte-exact claim.
_MEM_MODEL_BAND = (0.5, 2.0)


def test_predicted_peak_within_band_of_compiled_step():
    """``Trainer.predicted_peak_bytes()`` against what XLA allocates for
    the same step: ``memory_analysis()`` of the compiled executable,
    arguments + outputs + temporaries - aliases."""
    from tools.stepcost import build_cost_trainer, compile_step
    trainer, batch_vals, _ = build_cost_trainer()
    predicted = int(trainer.predicted_peak_bytes())
    mem = compile_step(trainer, batch_vals).memory_analysis()
    measured = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert measured > 0
    lo, hi = _MEM_MODEL_BAND
    assert lo <= predicted / measured <= hi, (predicted, measured)
