"""Static collective-communication analyzer: comm-plan extraction with
layer provenance, the static byte predictor's exact agreement with
``collectives.lowp_comm_bytes`` across the f32/bf16 x replicated/ZeRO
corners, one crafted fixture per comm rule (positive + clean), the
rank-divergence AST rule, the HEAD zero-error sweep via the CLI gate,
and the cross-rank plan-parity check (in-process pair + the two-process
digest-mismatch drill asserting the loud pre-step error)."""
import json
import os
import subprocess
import sys
import threading
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import elastic, parallel
from mxnet_tpu.analysis import comm_passes
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.collectives import (collective_wire_bytes,
                                            lowp_comm_bytes)
from jax import shard_map

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=240, **kw):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, cwd=_ROOT, timeout=timeout, **kw)


def _find(report, rule, severity=None):
    return [f for f in report.findings if f.rule == rule
            and (severity is None or f.severity == severity)]


def _mesh(n=2, axis="data"):
    return parallel.make_mesh({axis: n}, jax.devices()[:n])


def _mlp_trainer(zero, grad_dtype, n=2):
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=512, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    t = parallel.Trainer(
        sym, mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9),
        mesh=_mesh(n), zero=zero, grad_dtype=grad_dtype)
    t.bind(data_shapes={"data": (8, 600)},
           label_shapes={"softmax_label": (8,)})
    t.init_params(mx.init.Xavier())
    return t


# ======================================================================
# comm-plan extraction
def test_trainer_step_plan_nonempty_with_provenance():
    """The ZeRO-1 + bf16 fused step's plan: the shard_map'd gradient
    wire is visible statically — bf16 all_to_all per param leaf, each
    attributed to the grad_allreduce_bf16 scope INSIDE the shard_map
    body (the recursion fix), and the digest is deterministic."""
    t = _mlp_trainer(zero=1, grad_dtype="bf16")
    plan = t.comm_plan()
    assert plan, "ZeRO-1 + bf16 must issue collectives"
    assert all(e.primitive == "all_to_all" for e in plan)
    assert all(e.dtype == "bfloat16" for e in plan)
    assert all(e.axis == "data" for e in plan)
    assert all(e.layer == "grad_allreduce_bf16" for e in plan)
    # keep_shard: the zero plan never gathers the reduced grads
    assert not any(e.primitive == "all_gather" for e in plan)
    assert comm_passes.plan_digest(plan) == \
        comm_passes.plan_digest(t.comm_plan())


def test_plan_digest_differs_across_configs():
    d = {}
    for zero, gd in ((0, "f32"), (0, "bf16"), (1, "bf16")):
        d[(zero, gd)] = comm_passes.plan_digest(
            _mlp_trainer(zero, gd).comm_plan())
    assert d[(0, "f32")] != d[(0, "bf16")] != d[(1, "bf16")]


def test_scan_trip_count_multiplies_wire_bytes():
    """A collective inside a scan body predicts bytes x trip count (the
    pipeline's per-tick stage hop)."""
    mesh = _mesh(2, "pipe")

    def per_device(xs):
        def tick(carry, x):
            y = lax.ppermute(carry + x, "pipe", [(0, 1), (1, 0)])
            return y, y
        out, _ = lax.scan(tick, jnp.zeros(xs.shape[1:]), xs)
        return out

    fn = shard_map(per_device, mesh=mesh, in_specs=P(),
                   out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((5, 8, 4), np.float32))
    plan = comm_passes.extract_comm_plan(jaxpr, {"pipe": 2})
    (entry,) = plan
    assert entry.primitive == "ppermute" and entry.repeat == 5
    assert entry.wire_bytes == 5 * 8 * 4 * 4   # 5 ticks x 32 f32 elems


# ======================================================================
# static byte predictor vs the analytic gradient-wire model
@pytest.mark.parametrize("zero,grad_dtype", [(0, "f32"), (1, "f32"),
                                             (0, "bf16"), (1, "bf16")])
def test_comm_model_matches_analytic(zero, grad_dtype):
    """EXACT agreement between the plan's predicted wire bytes and
    ``Trainer.grad_comm_bytes_per_step`` on every corner — for bf16 the
    plan side is genuinely extracted from the jaxpr, so this pins the
    byte model to ``collectives.lowp_comm_bytes``."""
    t = _mlp_trainer(zero, grad_dtype)
    assert comm_passes.plan_wire_bytes(t.comm_plan()) == \
        t.grad_comm_bytes_per_step()


def test_collective_wire_bytes_composes_lowp_model():
    """``lowp_comm_bytes``'s per-leaf figures decompose into the
    per-primitive predictor: divisible leaf = all_to_all of the full
    leaf + all_gather of the summed 1/n shard; keep_shard drops the
    gather; non-divisible leaf = the all_gather fallback."""
    n = 4
    for shape in ((512, 600), (16, 3), (128,)):
        size = int(np.prod(shape))
        d0 = shape[0]
        if d0 >= n and d0 % n == 0:
            rs = collective_wire_bytes("all_to_all", size, 2, n)
            ag = collective_wire_bytes("all_gather", size // n, 2, n)
            assert rs + ag == lowp_comm_bytes(shape, n, 2)
            assert rs == lowp_comm_bytes(shape, n, 2, keep_shard=True)
        else:
            assert collective_wire_bytes("all_gather", size, 2, n) == \
                lowp_comm_bytes(shape, n, 2)
    # the f32 SPMD psum is the ring all-reduce model
    assert collective_wire_bytes("psum", 1000, 4, n) == \
        int(2 * (n - 1) / n * 4000)


# ======================================================================
# rule fixtures: one positive + one clean case each
def test_f32_wire_fires_on_f32_data_collective():
    mesh = _mesh(2)
    big = jax.ShapeDtypeStruct((1024, 600), np.float32)   # 2.4 MB f32

    def prog(x):
        with jax.named_scope("grads"):
            return shard_map(lambda v: lax.psum(v, "data"), mesh=mesh,
                             in_specs=P("data"), out_specs=P(),
                             check_vma=False)(x)

    jaxpr = jax.make_jaxpr(prog)(big)
    rep = comm_passes.lint_comm(
        jaxpr, model="crafted", axis_sizes={"data": 2},
        config={"grad_dtype": "bf16"})
    errs = _find(rep, "f32-wire", "error")
    assert len(errs) == 1
    assert errs[0].layer == "grads"          # scope outside the body
    assert "float32 psum" in errs[0].message
    # clean 1: the same traffic at bf16 wire dtype
    def prog16(x):
        return shard_map(
            lambda v: lax.psum(v.astype(jnp.bfloat16), "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P(),
            check_vma=False)(x)
    rep = comm_passes.lint_comm(
        jax.make_jaxpr(prog16)(big), model="crafted",
        axis_sizes={"data": 2}, config={"grad_dtype": "bf16"})
    assert not _find(rep, "f32-wire")
    # clean 2: f32 wire is the DECLARED policy
    rep = comm_passes.lint_comm(
        jaxpr, model="crafted", axis_sizes={"data": 2},
        config={"grad_dtype": "f32"})
    assert not _find(rep, "f32-wire")


def _rs_ag_prog(mesh, keep_shard):
    """The lowp reduce-scatter spelling (all_to_all + f32 sum) with —
    or without — the thrashing all-gather behind it."""
    def local(x):
        g16 = x.astype(jnp.bfloat16)
        chunks = lax.all_to_all(g16, "data", split_axis=0,
                                concat_axis=0, tiled=True)
        summed = chunks.reshape((2, x.shape[0] // 2) + x.shape[1:]) \
                       .astype(jnp.float32).sum(axis=0)
        if keep_shard:
            return summed
        return lax.all_gather(summed.astype(jnp.bfloat16), "data",
                              axis=0, tiled=True).astype(jnp.float32)

    out_spec = P("data") if keep_shard else P()
    return shard_map(local, mesh=mesh, in_specs=P(), out_specs=out_spec,
                     check_vma=False)


def test_resharding_thrash_fires_on_gather_after_scatter():
    mesh = _mesh(2)
    sds = jax.ShapeDtypeStruct((1024, 600), np.float32)
    jaxpr = jax.make_jaxpr(_rs_ag_prog(mesh, keep_shard=False))(sds)
    rep = comm_passes.lint_comm(jaxpr, model="crafted",
                                axis_sizes={"data": 2},
                                config={"zero": 1})
    errs = _find(rep, "resharding-thrash", "error")
    assert len(errs) == 1
    assert "all_to_all+sum reduce-scatter" in errs[0].message
    # clean 1: keep_shard — the zero plan consumes the owned shard
    rep = comm_passes.lint_comm(
        jax.make_jaxpr(_rs_ag_prog(mesh, keep_shard=True))(sds),
        model="crafted", axis_sizes={"data": 2}, config={"zero": 1})
    assert not _find(rep, "resharding-thrash")
    # clean 2: same gather, zero OFF — rs->ag IS the all-reduce then
    rep = comm_passes.lint_comm(jaxpr, model="crafted",
                                axis_sizes={"data": 2},
                                config={"zero": 0})
    assert not _find(rep, "resharding-thrash")


def test_comm_budget_ratchet():
    t = _mlp_trainer(zero=1, grad_dtype="bf16")
    plan = t.comm_plan()
    gb = comm_passes.plan_wire_gb(plan)
    # regression past tolerance: error
    rep = comm_passes.lint_comm(None, model="t", plan=plan,
                                config={"comm_baseline_gb": gb / 2,
                                        "comm_tolerance_pct": 3.0})
    errs = _find(rep, "comm-budget", "error")
    assert len(errs) == 1 and "regressed" in errs[0].message
    # within tolerance: silent
    rep = comm_passes.lint_comm(None, model="t", plan=plan,
                                config={"comm_baseline_gb": gb * 1.01,
                                        "comm_tolerance_pct": 3.0})
    assert not _find(rep, "comm-budget")
    # improvement past tolerance: INFO nudge to ratchet down
    rep = comm_passes.lint_comm(None, model="t", plan=plan,
                                config={"comm_baseline_gb": gb * 2,
                                        "comm_tolerance_pct": 3.0})
    infos = _find(rep, "comm-budget", "info")
    assert len(infos) == 1 and "ratchet" in infos[0].message


# ======================================================================
# rank-divergent-collective (source level)
def test_rank_divergence_fires_with_provenance(tmp_path):
    pkg = tmp_path / "fake_pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(textwrap.dedent("""\
        import jax

        def sync(x, rank):
            if rank == 0:
                x = jax.lax.psum(x, "data")
            return x
    """))
    (pkg / "clean.py").write_text(textwrap.dedent("""\
        import jax

        def sync(x, n_workers):
            if n_workers > 1:
                x = jax.lax.psum(x, "data")     # world-size agreed
            if jax.process_index() == 0:
                print("rank 0 logs, no collective here")
            return x
    """))
    (pkg / "suppressed.py").write_text(textwrap.dedent("""\
        def save(kv, rank):
            if rank == 0:
                kv.barrier()  # comm: ok deliberate rank-0 commit point
            return kv
    """))
    findings = comm_passes.scan_rank_divergence(str(pkg))
    errs = [f for f in findings
            if f.rule == "rank-divergent-collective"]
    assert len(errs) == 1
    assert errs[0].node.startswith("fake_pkg/bad.py:")
    assert errs[0].op == "psum"
    assert "'rank'" in errs[0].message


def test_rank_divergence_head_tree_is_clean():
    errs = [f for f in comm_passes.scan_rank_divergence()
            if f.severity == "error"]
    assert not errs, [f.format() for f in errs]


# ======================================================================
# cross-rank plan parity
def _coord(tmp_path, rank, n=2, **kw):
    kw.setdefault("hb_timeout", 5.0)
    kw.setdefault("step_timeout", 10.0)
    kw.setdefault("check_interval", 0.0)
    kw.setdefault("join_grace", 60.0)
    return elastic.ElasticCoordinator(rank=rank, num_workers=n,
                                      directory=str(tmp_path), **kw)


def test_plan_parity_agreeing_ranks_enter(tmp_path):
    plan = ["psum|data|float32|1000|x1", "all_gather|data|bfloat16|10|x1"]
    c0, c1 = _coord(tmp_path, 0), _coord(tmp_path, 1)
    c0.publish_comm_plan(plan)
    c1.publish_comm_plan(plan)
    errs = []

    def run(c):
        try:
            c.guard(1)
        except Exception as e:                  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=run, args=(c,)) for c in (c0, c1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs, errs
    c0.close()
    c1.close()


def test_plan_parity_mismatch_is_loud_and_names_the_divergence(tmp_path):
    c0, c1 = _coord(tmp_path, 0), _coord(tmp_path, 1)
    shared = "all_to_all|data|bfloat16|307200|x1"
    c0.publish_comm_plan([shared, "psum|data|float32|512|x1"])
    c1.publish_comm_plan([shared, "all_gather|data|float32|512|x1"])
    with pytest.raises(MXNetError) as err:
        c0.guard(1)
    msg = str(err.value)
    assert "comm-plan parity check FAILED" in msg
    assert "rank 1" in msg                      # the diverging peer
    assert "index 1" in msg                     # first differing entry
    assert "psum|data|float32|512|x1" in msg
    assert "all_gather|data|float32|512|x1" in msg
    c0.close()
    c1.close()


def test_plan_parity_untraced_peer_downgrades_to_warning(tmp_path):
    """A rank whose plan could not be traced publishes the UNTRACED
    sentinel (Module.fit's fallback): peers log, they don't die — a
    lint-trace hiccup on one rank must not kill the healthy fleet."""
    c0, c1 = _coord(tmp_path, 0), _coord(tmp_path, 1)
    c0.publish_comm_plan(["psum|data|float32|1000|x1"])
    c1.publish_comm_plan([], digest=elastic.COMM_PLAN_UNTRACED)
    errs = []

    def run(c):
        try:
            c.guard(1)
        except Exception as e:                  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=run, args=(c,)) for c in (c0, c1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs, errs
    c0.close()
    c1.close()


def test_plan_parity_missing_peer_refuses(tmp_path):
    c0 = _coord(tmp_path, 0)
    c0.publish_comm_plan(["psum|data|float32|4|x1"])
    c0.comm_parity_timeout = 0.3
    # keep rank 1's heartbeat alive so the guard reaches the parity
    # check instead of shrinking the world first
    from mxnet_tpu import health
    h1 = health.Heartbeat(1, directory=str(tmp_path), interval=0.05)
    try:
        with pytest.raises(MXNetError) as err:
            c0.guard(1)
        assert "published no comm plan" in str(err.value)
    finally:
        h1.stop()
        c0.close()


_DRILL = textwrap.dedent("""\
    import os, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, %(root)r)
    from mxnet_tpu import elastic

    rank = int(sys.argv[1])
    coord = elastic.ElasticCoordinator(
        rank=rank, num_workers=2, directory=sys.argv[2],
        hb_timeout=10.0, step_timeout=20.0, check_interval=0.0,
        join_grace=60.0)
    # the classic rank-divergent program: rank 1 would issue an extra
    # collective — statically visible in its comm plan
    plan = ["all_to_all|data|bfloat16|307200|x1"]
    if rank == 1:
        plan.append("all_gather|data|float32|307200|x1")
    coord.publish_comm_plan(plan)
    try:
        coord.guard(1)
    except Exception as e:
        print("PARITY_ERROR rank=%%d: %%s" %% (rank, e))
        sys.exit(17)
    print("ENTERED rank=%%d" %% rank)
    sys.exit(0)
""")


def test_two_process_digest_mismatch_drill(tmp_path):
    """The acceptance drill: two real processes, rank 1 deliberately
    divergent — both fail FAST with the digest-mismatch MXNetError
    before any collective runs, instead of wedging."""
    script = tmp_path / "drill.py"
    script.write_text(_DRILL % {"root": _ROOT})
    shared = tmp_path / "shared"
    shared.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_FAULTS", None)
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(shared)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=_ROOT, env=env) for r in (0, 1)]
    outs = [p.communicate(timeout=150)[0] for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 17, (r, p.returncode, out)
        assert "PARITY_ERROR rank=%d" % r in out
        assert "comm-plan parity check FAILED" in out
        assert "ENTERED" not in out
    # the error names the diverging rank and the first differing entry
    assert "rank 1" in outs[0]
    assert "all_gather|data|float32|307200|x1" in outs[0]


# ======================================================================
# CLI gate
def test_cli_head_sweep_clean_and_gate_ok():
    """The zero-error sweep: every comm target at HEAD is clean and the
    checked-in COMM_BASELINE.json gate passes."""
    res = _run(["tools/comm_lint.py", "--check", "--json"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "baseline gate OK" in res.stdout
    start = res.stdout.index("{")
    end = res.stdout.rindex("}") + 1
    reports = json.loads(res.stdout[start:end])
    assert reports["trainer-step"]["counts"]["error"] == 0
    assert reports["comm-source"]["counts"]["error"] == 0
    # the acceptance plan: non-empty with layer provenance
    assert "grad_allreduce_bf16" in res.stdout


def test_cli_gate_fails_on_injected_f32_wire():
    res = _run(["tools/comm_lint.py", "trainer-step", "--inject",
                "f32-wire", "--check"])
    assert res.returncode == 1, res.stdout + res.stderr
    assert "f32-wire" in res.stdout
    assert "baseline gate FAILED" in res.stdout
