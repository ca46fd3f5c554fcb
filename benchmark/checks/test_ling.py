"""``ling3flash_train`` at a size the CPU holds: the same files, the
sizes cut (three blocks: a dense and an expert one that mix by the
delta rule, an expert one that mixes by latent attention at 24 / 16 wide
heads; d 64, 32 experts in 4 groups with 4 held, vocabulary 512, 128
positions, so two chunks); a sound run, the step broken underneath, the
float8 control; its operation counts by hand; its two metrics' files
against a hand-built trace.  No metric is printed."""
import time

import pytest

from lib import jobs, spec
import test_rehearse as rehearse
import test_tracered as tracered
import tiny


def ling(batch=4, seq=128):
    cfg = tiny._load("configs", "ling-3.0-flash")
    cfg.update(hidden_size=64, num_attention_heads=2, head_dim=16,
               kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=160,
               moe_intermediate_size=48, num_experts=4,
               num_experts_per_tok=4, n_group=4, topk_group=2,
               num_hidden_layers=3, vocab_size=512, layer_group_size=3)
    cfg["published"] = dict(cfg["published"], num_experts=32)
    # published layers 0 (dense), 3 and 5 (the period's last: MLA)
    cfg["deployment"] = dict(cfg["deployment"], layers_kept=[0, 3, 5])
    cfg["symbol"]["kwargs"] = dict(
        vocab_size=512, seq_len=seq, hidden_size=64,
        layer_types="kda,kda,mla", first_k_dense=1, heads_held=2,
        head_dim=16, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
        moe_intermediate_size=48, num_experts=32, experts_held=4,
        first_expert=0, num_experts_per_tok=4, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, rope_theta=6e6)
    cfg["input"] = {"kind": "tokens", "seq_len": seq, "vocab": 512}
    tr = tiny._load("traffic", TRAFFIC)
    tr.update(batch=batch, samples_per_row=seq, reference_row_block=2,
              env={})
    return tiny._cell("tiny_ling", cfg, tr, "ling3flash_train",
                      "train_tokens_per_s", "tokens/s")


TRAFFIC = [w["traffic"] for w in spec.benchmark()["workloads"]
           if w["name"] == "ling3flash_train"][0]


def test_the_cells_files_load_by_name():
    cell = spec.Cell("ling3flash_train")
    assert cell.chips == 1 and cell.traffic["job"] == "train"
    assert cell.config["symbol"]["network"] == "bailing-hybrid"
    assert {m["name"] for m in cell.end_to_end} \
        == {"train_tokens_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert names[-2:] == ["kernel.kda_roofline.tok", "ops.kda_ms.tok"]
    assert "ops.mtp_ms.tok" not in names and len(names) == 15
    assert set(cell.limits["limits"]) == {
        "grad_norm_gap", "change_norm_gap", "grad_norm_gap_median",
        "change_norm_gap_median"}
    for name in ("costs", "init", "loss", "param_shapes"):
        assert callable(getattr(cell.reference, name))


@pytest.fixture(scope="module")
def sound():
    return rehearse.a_sound_run(ling())


def test_tiny_ling_runs_and_is_correct(sound):
    cell, res, _ = sound
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(res["checked"]) == set(cell.limits["limits"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_tiny_ling_with_a_broken_step_is_not_correct(sound, monkeypatch,
                                                      fault):
    cell, _, _ = sound
    rehearse.Broken(monkeypatch, fault)
    res = jobs.run(cell, rehearse.SEED, 0.3, False, time.time(),
                   rehearse.quiet)
    assert res["correct"] is False, res["checked"]


def test_tiny_ling_faults_and_control_planted_in_the_reference(sound):
    cell, _, want = sound
    for kw in ({"fault": "half_batch"}, {"fault": "state_unchanged"},
               {"cast": "fp8"}):
        rows = rehearse.judged(cell, rehearse.reference_numbers(cell, **kw),
                               want)
        assert not all(held for _, _, _, held in rows), (kw, rows)
    rows = rehearse.judged(cell, rehearse.reference_numbers(cell), want)
    assert all(held for _, _, _, held in rows), rows


def test_costs_of_the_rule_and_of_one_expert_layer_by_hand():
    """At the cell's 1 x 4,096 tokens: a KDA layer's rule is 64 chunks
    of 8 heads, 64^2 (3 x 128 + 2 x 128) + 6 x 64 x 128 x 128 operations
    each forward and twice that back; the experts at the expected 4,096
    x 8 x 8/512 = 512 entries; the one MLA layer at 192 / 128."""
    cfg = tiny._load("configs", "ling-3.0-flash")
    c = spec.reference(cfg["reference"]).costs(cfg, 1)
    by = c["by_layer"]
    assert by["l0_kda_core"] == 3 * 64 * 8 * (4096 * 640 + 6 * 64 * 16384)
    assert c["kda"]["flops"] == 6 * by["l0_kda_core"]
    assert c["kda"]["bytes"] == 6 * 3 * (2 * 4096 * 8 * 641
                                         + 4 * 512 * 128 * 128)
    assert by["l2_moe_experts"] == 6 * 512 * 3 * 2560 * 768
    assert by["l2_moe_router"] == 6 * 4096 * 2560 * 512
    assert by["l4_attn"] == 6 * 8 * (4096 * 4096 // 2) * (192 + 128)
    assert c["attention"]["flops"] == by["l4_attn"]
    assert by["head"] == 6 * 4096 * 2560 * 19648
    assert c["model_flops"] == sum(c[k]["flops"] for k in
                                   ("matmul", "experts", "attention", "kda"))


def test_the_new_metrics_read_a_trace_or_nothing():
    """The two metrics this cell brings: the mixer's time is a number (0
    where no operation ran under such a scope); the rule's roofline has
    nothing to read where there is no such cost or no such scope, as on
    the parent, and says so by returning nothing."""
    bench = spec.benchmark()
    ctx = {"trace": tracered.hand_trace(),
           "device": {"count": 2, "memory_peak_bytes": 7e9},
           "costs": {"model_flops": 1e6}, "peaks": spec.peaks("TPU v5 lite")}
    mine = {m["name"]: m for m in spec.Cell("ling3flash_train",
                                            bench).per_layer}

    def read(name):
        m = mine[name]
        return spec.reader(m["reader"]).read(ctx, **m.get("args", {}))
    assert read("ops.kda_ms.tok") == 0
    assert read("kernel.kda_roofline.tok") is None
    ctx["costs"]["kda"] = {"flops": 197e12 * 20e-9, "bytes": 1.0}
    assert read("kernel.kda_roofline.tok") is None       # no such scope
    # the rule's scope, forward and backward; the mixer's other nodes
    ctx["trace"] = tracered.Trace(
        {0: [tracered.Op(0, 60, "l1_kda_core_fwd_while", tracered.OTHER,
                         "l1_kda_core"),
             tracered.Op(60, 40, "l1_kda_core_bwd_fusion", tracered.OTHER,
                         "l1_kda_core"),
             tracered.Op(100, 50, "l1_kda_q_conv_fwd_fusion", tracered.OTHER,
                         "l1_kda_q_conv"),
             tracered.Op(150, 30, "l1_moe_shared_up_fwd_convolution",
                         tracered.DOT, "l1_moe_shared_up")]},
        [], (0, 200), steps=1)
    ctx["device"]["count"] = 1
    assert read("kernel.kda_roofline.tok") == pytest.approx(100 * 20 / 100)
    assert read("ops.kda_ms.tok") == pytest.approx(150e-6)
