"""``laguna_xs2_train`` at a size the CPU holds: the same files, the sizes
cut (three blocks: a dense one that attends fully, two expert ones that
attend in a window of 16 positions; d 64, 4 and 8 query heads of 16 over
2 key/value heads, 16 experts with 4 held, vocabulary 512, 64 positions);
a sound run, the step broken underneath, the float8 control; its
operation counts by hand; its two metrics' files against a hand-built
trace.  No metric is printed."""
import time

import pytest

from lib import jobs, spec
import test_rehearse as rehearse
import test_tracered as tracered
import tiny

TINY_ROPE = {
    "full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                       "factor": 8, "original_max_position_embeddings": 16,
                       "beta_slow": 1, "beta_fast": 32,
                       "attention_factor": 1.2, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}


def laguna(batch=2, seq=64):
    cfg = tiny._load("configs", "laguna-xs.2")
    heads = [4 if k == "full_attention" else 8 for k in cfg["layer_types"]]
    cfg.update(hidden_size=64, num_key_value_heads=2, head_dim=16,
               sliding_window=16, intermediate_size=160,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_experts=4, num_experts_per_tok=4, num_hidden_layers=3,
               vocab_size=512,
               num_attention_heads_per_layer=heads, rope_parameters=TINY_ROPE)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    # published layers 0 (full, dense), 1 and 2 (sliding, experts)
    cfg["deployment"] = dict(cfg["deployment"], layers_kept=[0, 1, 2])
    cfg["symbol"]["kwargs"] = dict(
        vocab_size=512, seq_len=seq, hidden_size=64,
        layer_types="full_attention,sliding_attention,sliding_attention",
        heads_per_layer="4,8,8", num_kv_heads=2, head_dim=16,
        sliding_window=16, rope_parameters=TINY_ROPE, num_dense_layers=1,
        intermediate_size=160, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_experts=16, experts_held=4,
        first_expert=0, num_experts_per_tok=4, routed_scaling_factor=2.5,
        rms_norm_eps=1e-6)
    cfg["input"] = {"kind": "tokens", "seq_len": seq, "vocab": 512}
    tr = tiny._load("traffic", TRAFFIC)
    tr.update(batch=batch, samples_per_row=seq, reference_row_block=1,
              env={})
    return tiny._cell("tiny_laguna", cfg, tr, "laguna_xs2_train",
                      "train_tokens_per_s", "tokens/s")


TRAFFIC = [w["traffic"] for w in spec.benchmark()["workloads"]
           if w["name"] == "laguna_xs2_train"][0]


def test_the_cells_files_load_by_name():
    cell = spec.Cell("laguna_xs2_train")
    assert cell.chips == 1 and cell.traffic["job"] == "train"
    assert cell.traffic["samples_per_row"] == 8192
    assert cell.config["symbol"]["network"] == "laguna"
    assert {m["name"] for m in cell.end_to_end} \
        == {"train_tokens_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert names[-2:] == ["kernel.window_roofline.tok",
                          "attention.window_live_share.tok"]
    for name in ("kernel.flash_roofline.tok", "kernel.expert_roofline.tok",
                 "ops.moe_route_ms.tok", "moe.load_max_over_mean.tok",
                 "step.mfu.tok", "program.loads_at_setup"):
        assert name in names
    for name in ("ops.kda_ms.tok", "kernel.kda_roofline.tok",
                 "ops.mtp_ms.tok", "ops.loop_exit_ms.tok",
                 "ops.sconv_ms.tok", "kernel.sconv_roofline.tok"):
        assert name not in names
    assert len(names) == 18
    assert set(cell.limits["limits"]) == {
        "grad_norm_gap", "change_norm_gap", "grad_norm_gap_median",
        "change_norm_gap_median"}
    for name in ("costs", "init", "loss", "param_shapes"):
        assert callable(getattr(cell.reference, name))


@pytest.fixture(scope="module")
def sound():
    return rehearse.a_sound_run(laguna())


def test_tiny_laguna_runs_and_is_correct(sound):
    cell, res, _ = sound
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(res["checked"]) == set(cell.limits["limits"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_tiny_laguna_with_a_broken_step_is_not_correct(sound, monkeypatch,
                                                       fault):
    cell, _, _ = sound
    rehearse.Broken(monkeypatch, fault)
    res = jobs.run(cell, rehearse.SEED, 0.3, False, time.time(),
                   rehearse.quiet)
    assert res["correct"] is False, res["checked"]


def test_tiny_laguna_faults_and_control_planted_in_the_reference(sound):
    cell, _, want = sound
    for kw in ({"fault": "half_batch"}, {"fault": "state_unchanged"},
               {"cast": "fp8"}):
        rows = rehearse.judged(cell, rehearse.reference_numbers(cell, **kw),
                               want)
        assert not all(held for _, _, _, held in rows), (kw, rows)
    rows = rehearse.judged(cell, rehearse.reference_numbers(cell), want)
    assert all(held for _, _, _, held in rows), rows


def test_costs_of_the_two_attention_kinds_and_one_expert_layer_by_hand():
    """At the cell's 1 x 8,192 tokens: a full layer's 48 heads over the
    causal pairs, a window layer's 64 over the live pairs of a window of
    512 (8,192 x 512 - 512 x 511 / 2 a head); the experts at the
    expected 8,192 x 8 x 16/256 = 4,096 entries."""
    cfg = tiny._load("configs", "laguna-xs.2")
    c = spec.reference(cfg["reference"]).costs(cfg, 1)
    by = c["by_layer"]
    assert by["l0_attn"] == 6 * 2 * 48 * (8192 * 8192 // 2) * 128
    assert by["l1_attn"] == 6 * 2 * 64 * (8192 * 512 - 512 * 511 // 2) * 128
    assert c["attention"]["flops"] == by["l0_attn"] + by["l4_attn"]
    assert c["window"]["flops"] == 3 * by["l1_attn"]
    assert c["window"]["bytes"] == 3 * 2 * 4 * 8192 * 128 * (64 + 8)
    assert by["l2_moe_experts"] == 6 * 4096 * 3 * 2048 * 512
    assert by["l1_attn_gate"] == 6 * 8192 * 2048 * 64
    assert c["model_flops"] == sum(c[k]["flops"] for k in
                                   ("matmul", "experts", "attention",
                                    "window"))


def test_the_new_metrics_read_a_trace_or_nothing():
    """The two metrics this cell brings: the window kernels' roofline has
    nothing to read where there is no such cost or no such scope, as on
    a program without window layers, and says so by returning nothing;
    the live share is the program's gauge, or nothing where it has
    none."""
    from mxnet_tpu import obs
    bench = spec.benchmark()
    ctx = {"trace": tracered.hand_trace(),
           "device": {"count": 2, "memory_peak_bytes": 7e9},
           "costs": {"model_flops": 1e6}, "peaks": spec.peaks("TPU v5 lite")}
    mine = {m["name"]: m for m in spec.Cell("laguna_xs2_train",
                                            bench).per_layer}

    def read(name):
        m = mine[name]
        return spec.reader(m["reader"]).read(ctx, **m.get("args", {}))
    assert read("kernel.window_roofline.tok") is None
    ctx["costs"]["window"] = {"flops": 197e12 * 30e-9, "bytes": 1.0}
    assert read("kernel.window_roofline.tok") is None     # no such scope
    ctx["trace"] = tracered.Trace(
        {0: [tracered.Op(0, 60, "l1_attn_window_fwd_custom-call",
                         tracered.OTHER, "l1_attn_window"),
             tracered.Op(60, 40, "l1_attn_attn_fwd_custom-call",
                         tracered.OTHER, "l1_attn_attn"),
             tracered.Op(100, 50, "l1_attn_gate_fwd_convolution",
                         tracered.DOT, "l1_attn_gate")]},
        [], (0, 200), steps=1)
    ctx["device"]["count"] = 1
    assert read("kernel.window_roofline.tok") == pytest.approx(100 * 30 / 60)
    obs.gauge("attention.window.live_share").set(0.5)
    assert read("attention.window_live_share.tok") == 0.5
