"""``ouro26b_train`` at a size the CPU holds: the same files, the sizes
cut (two layers run four times, d 64, two heads of 32, width 160,
vocabulary 512, 64 positions, every pass marked for recomputation); a
sound run, the step broken underneath, the float8 control; its operation
counts by hand; its three metrics' files against a hand-built trace.  No
metric is printed."""
import time

import pytest

from lib import jobs, spec
import test_rehearse as rehearse
import test_tracered as tracered
import tiny


def ouro(batch=4, seq=64):
    cfg = tiny._load("configs", "ouro-2.6b")
    cfg.update(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
               head_dim=32, intermediate_size=160, num_hidden_layers=2,
               vocab_size=512)
    cfg["symbol"]["kwargs"] = dict(
        vocab_size=512, seq_len=seq, hidden_size=64, num_layers=2,
        num_heads=2, head_dim=32, intermediate_size=160, loop_steps=4,
        exit_beta=0.1, rope_theta=1e6, rms_norm_eps=1e-6, segments=True)
    cfg["input"] = {"kind": "tokens", "seq_len": seq, "vocab": 512}
    tr = tiny._load("traffic", TRAFFIC)
    tr.update(batch=batch, samples_per_row=seq, reference_row_block=2,
              env={})
    return tiny._cell("tiny_ouro", cfg, tr, "ouro26b_train",
                      "train_tokens_per_s", "tokens/s")


TRAFFIC = [w["traffic"] for w in spec.benchmark()["workloads"]
           if w["name"] == "ouro26b_train"][0]


def test_the_cells_files_load_by_name():
    cell = spec.Cell("ouro26b_train")
    assert cell.chips == 1 and cell.traffic["job"] == "train"
    assert TRAFFIC == "tokens_b1_s4096"
    assert cell.config["symbol"]["network"] == "loop-lm"
    assert cell.config["symbol"]["kwargs"]["segments"] is True
    assert {m["name"] for m in cell.end_to_end} \
        == {"train_tokens_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert names[-3:] == ["ops.loop_exit_ms.tok", "ops.recompute_ms.tok",
                          "loop.expected_steps.tok"]
    assert {"kernel.flash_roofline.tok", "kernel.matmul_roofline.tok",
            "step.mfu.tok", "ops.nonmatmul_ms.tok"} <= set(names)
    assert not {"kernel.expert_roofline.tok", "ops.moe_route_ms.tok",
                "ops.mtp_ms.tok", "moe.load_max_over_mean.tok",
                "kernel.kda_roofline.tok", "ops.kda_ms.tok"} & set(names)
    assert len(names) == 13
    for name in ("costs", "init", "loss", "param_shapes"):
        assert callable(getattr(cell.reference, name))
    # nothing the older cells read has moved
    for older in ("gpt2m_train", "glm47flash_train", "ling3flash_train"):
        had = [m["name"] for m in spec.Cell(older).per_layer]
        assert not set(names[-3:]) & set(had)


@pytest.fixture(scope="module")
def sound():
    return rehearse.a_sound_run(ouro())


def test_tiny_ouro_runs_and_is_correct(sound):
    cell, res, _ = sound
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(res["checked"]) == set(cell.limits["limits"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_tiny_ouro_with_a_broken_step_is_not_correct(sound, monkeypatch,
                                                      fault):
    cell, _, _ = sound
    rehearse.Broken(monkeypatch, fault)
    res = jobs.run(cell, rehearse.SEED, 0.3, False, time.time(),
                   rehearse.quiet)
    assert res["correct"] is False, res["checked"]


def test_tiny_ouro_faults_and_control_planted_in_the_reference(sound):
    cell, _, want = sound
    for kw in ({"fault": "half_batch"}, {"fault": "state_unchanged"},
               {"cast": "fp8"}):
        rows = rehearse.judged(cell, rehearse.reference_numbers(cell, **kw),
                               want)
        assert not all(held for _, _, _, held in rows), (kw, rows)
    rows = rehearse.judged(cell, rehearse.reference_numbers(cell), want)
    assert all(held for _, _, _, held in rows), rows


def test_costs_of_the_loop_by_hand():
    """At the cell's 1 x 4,096 tokens: four passes of every block, 7
    products and a causal attention node of 16 heads of 128 each, 4 head
    passes over all 49,152 rows of the vocabulary, 3 gates; nothing
    recomputed."""
    cfg = tiny._load("configs", "ouro-2.6b")
    layers = cfg["num_hidden_layers"]
    c = spec.reference(cfg["reference"]).costs(cfg, 1)
    by = c["by_layer"]
    assert by["u3_l2_attn_q"] == by["u1_l%d_attn_o" % (layers - 1)] \
        == 6 * 4096 * 2048 * 2048
    assert by["u2_l0_mlp_down"] == 6 * 4096 * 2048 * 5632
    assert by["u4_exit_head"] == 6 * 4096 * 2048 * 49152
    assert by["u1_l0_attn"] == 6 * 2 * 16 * 128 * (4096 * 4096 // 2)
    assert c["attention"]["flops"] == 4 * layers * by["u1_l0_attn"]
    assert sum(1 for n in by if n.endswith("_attn")) == 4 * layers
    assert sum(1 for n in by if n.endswith("exit_head")) == 4
    assert sum(1 for n in by if n.endswith("exit_gate")) == 3
    assert c["model_flops"] == c["matmul"]["flops"] + c["attention"]["flops"]
    # what the cut distorts: the head passes' share of the operations,
    # 3% in the whole model (BENCHMARK.json's why says which here)
    heads = 4 * by["u1_exit_head"] / c["model_flops"]
    assert heads == pytest.approx({6: 0.219, 5: 0.252, 4: 0.296}[layers],
                                  abs=1e-3)


def test_the_new_metrics_read_a_trace_or_nothing():
    """The three metrics this cell brings, on ``ops_ms`` and
    ``obs_gauge``.  The exit's time is a number (0 where no operation
    ran under such a scope): forward, run again and reverse.  The
    recomputation's is the time of the operations under ``remat.<node>``,
    a segment's forward operations run again in its backward pass, and
    not that of their reverse modes beside them (``remat_bwd.<node>``).
    The gauge has nothing to read until the program sets it."""
    bench = spec.benchmark()
    ctx = {"trace": tracered.hand_trace(),
           "device": {"count": 2, "memory_peak_bytes": 7e9},
           "costs": {"model_flops": 1e6}, "peaks": spec.peaks("TPU v5 lite")}
    mine = {m["name"]: m for m in spec.Cell("ouro26b_train",
                                            bench).per_layer}

    def read(name):
        m = mine[name]
        return spec.reader(m["reader"]).read(ctx, **m.get("args", {}))
    assert read("ops.loop_exit_ms.tok") == 0
    assert read("ops.recompute_ms.tok") == 0
    Op, OTHER, DOT = tracered.Op, tracered.OTHER, tracered.DOT
    ctx["trace"] = tracered.Trace(
        {0: [Op(0, 60, "u1_l0_attn_q_fwd_convolution", DOT, "u1_l0_attn_q"),
             Op(60, 40, "u1_exit_head_fwd_convolution", DOT, "u1_exit_head"),
             Op(100, 10, "u1_exit_rowloss_fwd_fusion", OTHER,
                "u1_exit_rowloss"),
             Op(110, 20, "exit_loss_dist_fwd_fusion", OTHER,
                "exit_loss_dist"),
             Op(130, 30, "tok_embed_fwd_gather", OTHER, "tok_embed"),
             # the backward pass of the segments: run again, and back
             Op(200, 45, "remat.u1_exit_head_bwd_convolution", DOT,
                "remat.u1_exit_head"),
             Op(245, 80, "remat_bwd.u1_exit_head_bwd_convolution", DOT,
                "remat_bwd.u1_exit_head"),
             Op(325, 65, "remat.u1_l0_attn_q_bwd_convolution", DOT,
                "remat.u1_l0_attn_q"),
             Op(390, 110, "remat_bwd.u1_l0_attn_q_bwd_convolution", DOT,
                "remat_bwd.u1_l0_attn_q"),
             Op(500, 5, "exit_loss_dist_bwd_fusion", OTHER,
                "exit_loss_dist")]},
        [], (0, 600), steps=2)
    # head 40 + 45 + 80, row loss 10, combination 20 + 5
    assert read("ops.loop_exit_ms.tok") == pytest.approx(200e-6 / 2)
    # the head and the projection run again: 45 + 65, and neither their
    # first run (40, 60) nor their reverse modes (80, 110)
    assert read("ops.recompute_ms.tok") == pytest.approx(110e-6 / 2)
    from mxnet_tpu import obs
    if "loop.expected_steps" not in obs.snapshot()["gauges"]:
        assert read("loop.expected_steps.tok") is None
    obs.gauge("loop.expected_steps").set(1.875)
    assert read("loop.expected_steps.tok") == 1.875
