"""``lfm2_24b_train`` at a size the CPU holds: the same files, the sizes
cut (three blocks: a dense one and an expert one that mix by the gated
short convolution, an expert one that mixes by attention of 4 query
heads over 2 key/value heads; d 64, 16 experts with 4 held, vocabulary
512, 128 positions); a sound run, the step broken underneath, the float8
control; its operation counts by hand; its two metrics' files against a
hand-built trace.  No metric is printed."""
import time

import pytest

from lib import jobs, spec
import test_rehearse as rehearse
import test_tracered as tracered
import tiny


def lfm2(batch=4, seq=128):
    cfg = tiny._load("configs", "lfm2-24b-a2b")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=160, moe_intermediate_size=48,
               num_experts=4, num_hidden_layers=3, vocab_size=512)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    # published layers 0 (convolution, dense), 2 (attention), 3
    cfg["deployment"] = dict(cfg["deployment"], layers_kept=[0, 2, 3])
    cfg["symbol"]["kwargs"] = dict(
        vocab_size=512, seq_len=seq, hidden_size=64,
        layer_types="conv,attention,conv", num_dense_layers=1, num_heads=4,
        num_kv_heads=2, conv_kernel=3, intermediate_size=160,
        moe_intermediate_size=48, num_experts=16, experts_held=4,
        first_expert=0, num_experts_per_tok=4, routed_scaling_factor=1.0,
        rope_theta=1e6, rms_norm_eps=1e-5)
    cfg["input"] = {"kind": "tokens", "seq_len": seq, "vocab": 512}
    tr = tiny._load("traffic", TRAFFIC)
    tr.update(batch=batch, samples_per_row=seq, reference_row_block=2,
              env={})
    return tiny._cell("tiny_lfm2", cfg, tr, "lfm2_24b_train",
                      "train_tokens_per_s", "tokens/s")


TRAFFIC = [w["traffic"] for w in spec.benchmark()["workloads"]
           if w["name"] == "lfm2_24b_train"][0]


def test_the_cells_files_load_by_name():
    cell = spec.Cell("lfm2_24b_train")
    assert cell.chips == 1 and cell.traffic["job"] == "train"
    assert cell.traffic["samples_per_row"] == 8192
    assert cell.config["symbol"]["network"] == "lfm2-moe"
    assert {m["name"] for m in cell.end_to_end} \
        == {"train_tokens_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert names[-2:] == ["ops.sconv_ms.tok", "kernel.sconv_roofline.tok"]
    for name in ("kernel.flash_roofline.tok", "kernel.expert_roofline.tok",
                 "ops.moe_route_ms.tok", "moe.load_max_over_mean.tok"):
        assert name in names
    for name in ("ops.kda_ms.tok", "kernel.kda_roofline.tok",
                 "ops.mtp_ms.tok", "ops.loop_exit_ms.tok",
                 "ops.recompute_ms.tok", "loop.expected_steps.tok"):
        assert name not in names
    assert len(names) == 18
    assert set(cell.limits["limits"]) == {
        "grad_norm_gap", "change_norm_gap", "grad_norm_gap_median",
        "change_norm_gap_median"}
    for name in ("costs", "init", "loss", "param_shapes"):
        assert callable(getattr(cell.reference, name))


@pytest.fixture(scope="module")
def sound():
    return rehearse.a_sound_run(lfm2())


def test_tiny_lfm2_runs_and_is_correct(sound):
    cell, res, _ = sound
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(res["checked"]) == set(cell.limits["limits"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_tiny_lfm2_with_a_broken_step_is_not_correct(sound, monkeypatch,
                                                     fault):
    cell, _, _ = sound
    rehearse.Broken(monkeypatch, fault)
    res = jobs.run(cell, rehearse.SEED, 0.3, False, time.time(),
                   rehearse.quiet)
    assert res["correct"] is False, res["checked"]


def test_tiny_lfm2_faults_and_control_planted_in_the_reference(sound):
    cell, _, want = sound
    for kw in ({"fault": "half_batch"}, {"fault": "state_unchanged"},
               {"cast": "fp8"}):
        rows = rehearse.judged(cell, rehearse.reference_numbers(cell, **kw),
                               want)
        assert not all(held for _, _, _, held in rows), (kw, rows)
    rows = rehearse.judged(cell, rehearse.reference_numbers(cell), want)
    assert all(held for _, _, _, held in rows), rows


def test_costs_of_the_convolution_and_of_one_expert_layer_by_hand():
    """At the cell's 1 x 8,192 tokens: a convolution mixer's gated core
    moves 11 x 8,192 x 2,048 two-byte elements (1.48 GB over the four);
    the experts at the expected 8,192 x 4 x 8/64 = 4,096 entries; the
    one attention layer at 32 query heads over 8 key/value heads."""
    cfg = tiny._load("configs", "lfm2-24b-a2b")
    c = spec.reference(cfg["reference"]).costs(cfg, 1)
    by = c["by_layer"]
    assert c["sconv"]["bytes"] == 4 * 2 * 11 * 8192 * 2048
    assert c["sconv"]["flops"] == 4 * by["l0_sconv"]
    assert by["l2_moe_experts"] == 6 * 4096 * 3 * 2048 * 1536
    assert by["l2_moe_router"] == 6 * 8192 * 2048 * 64
    assert by["l1_attn"] == 6 * 2 * 32 * (8192 * 8192 // 2) * 64
    assert c["attention"]["bytes"] == 2 * 4 * 8192 * 64 * (32 + 8)
    assert by["head"] == 6 * 8192 * 2048 * 8192
    assert c["model_flops"] == sum(c[k]["flops"] for k in
                                   ("matmul", "experts", "attention", "sconv"))


def test_the_new_metrics_read_a_trace_or_nothing():
    """The two metrics this cell brings: the mixer's time is a number (0
    where no operation ran under such a scope); the core's roofline has
    nothing to read where there is no such cost or no such scope, as on
    a program without these scopes, and says so by returning nothing."""
    bench = spec.benchmark()
    ctx = {"trace": tracered.hand_trace(),
           "device": {"count": 2, "memory_peak_bytes": 7e9},
           "costs": {"model_flops": 1e6}, "peaks": spec.peaks("TPU v5 lite")}
    mine = {m["name"]: m for m in spec.Cell("lfm2_24b_train",
                                            bench).per_layer}

    def read(name):
        m = mine[name]
        return spec.reader(m["reader"]).read(ctx, **m.get("args", {}))
    assert read("ops.sconv_ms.tok") == 0
    assert read("kernel.sconv_roofline.tok") is None
    ctx["costs"]["sconv"] = {"flops": 1.0, "bytes": 819e9 * 20e-9}
    assert read("kernel.sconv_roofline.tok") is None     # no such scope
    # the core's three nodes, forward and backward; the mixer's others
    ctx["trace"] = tracered.Trace(
        {0: [tracered.Op(0, 30, "l0_sconv_bx_fwd_fusion", tracered.OTHER,
                         "l0_sconv_bx"),
             tracered.Op(30, 40, "l0_sconv_taps_bwd_fusion", tracered.OTHER,
                         "l0_sconv_taps"),
             tracered.Op(70, 30, "l0_sconv_cz_fwd_fusion", tracered.OTHER,
                         "l0_sconv_cz"),
             tracered.Op(100, 50, "l0_sconv_in_fwd_convolution",
                         tracered.DOT, "l0_sconv_in"),
             tracered.Op(150, 30, "l1_attn_q_fwd_convolution",
                         tracered.DOT, "l1_attn_q")]},
        [], (0, 200), steps=1)
    ctx["device"]["count"] = 1
    assert read("kernel.sconv_roofline.tok") == pytest.approx(100 * 20 / 100)
    assert read("ops.sconv_ms.tok") == pytest.approx(150e-6)
