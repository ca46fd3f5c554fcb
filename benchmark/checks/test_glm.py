"""``glm47flash_train`` at a size the CPU holds: the same files, the
sizes cut (2 + 1 layers, d 64, 16 experts 4 held, vocabulary 512, 64
positions); a sound run, the step broken underneath, the float8
control; its operation counts by hand; its metrics' files against the
hand-built trace.  No metric is printed."""
import time

import pytest

from lib import jobs, spec
import test_rehearse as rehearse
import test_tracered as tracered
import tiny


def glm(batch=4, seq=64):
    cfg = tiny._load("configs", "glm-4.7-flash")
    cut = dict(hidden_size=64, num_attention_heads=2, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
               v_head_dim=32, intermediate_size=160,
               moe_intermediate_size=48, n_routed_experts=4,
               num_hidden_layers=2, vocab_size=512)
    cfg.update(cut)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["symbol"]["kwargs"] = dict(
        vocab_size=512, seq_len=seq, hidden_size=64, num_layers=2,
        first_k_dense=1, num_heads=2, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
        intermediate_size=160, moe_intermediate_size=48,
        n_routed_experts=16, experts_held=4, first_expert=0,
        num_experts_per_tok=4, routed_scaling_factor=1.8,
        rope_theta=1e6, mtp_layers=1, mtp_lambda=0.3)
    cfg["input"] = {"kind": "tokens", "seq_len": seq, "vocab": 512}
    tr = tiny._load("traffic", TRAFFIC)
    tr.update(batch=batch, samples_per_row=seq, reference_row_block=2,
              env={})
    return tiny._cell("tiny_glm", cfg, tr, "glm47flash_train",
                      "train_tokens_per_s", "tokens/s")


TRAFFIC = [w["traffic"] for w in spec.benchmark()["workloads"]
           if w["name"] == "glm47flash_train"][0]


@pytest.fixture(scope="module")
def sound():
    return rehearse.a_sound_run(glm())


def test_tiny_glm_runs_and_is_correct(sound):
    cell, res, _ = sound
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(res["checked"]) == set(cell.limits["limits"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_tiny_glm_with_a_broken_step_is_not_correct(sound, monkeypatch,
                                                     fault):
    cell, _, _ = sound
    rehearse.Broken(monkeypatch, fault)
    res = jobs.run(cell, rehearse.SEED, 0.3, False, time.time(),
                   rehearse.quiet)
    assert res["correct"] is False, res["checked"]


def test_tiny_glm_faults_and_control_planted_in_the_reference(sound):
    cell, _, want = sound
    for kw in ({"fault": "half_batch"}, {"fault": "state_unchanged"},
               {"cast": "fp8"}):
        rows = rehearse.judged(cell, rehearse.reference_numbers(cell, **kw),
                               want)
        assert not all(held for _, _, _, held in rows), (kw, rows)
    rows = rehearse.judged(cell, rehearse.reference_numbers(cell), want)
    assert all(held for _, _, _, held in rows), rows


def test_costs_of_one_expert_layer_by_hand():
    """Layer 2 at the cell's 1 x 4,096 tokens: the experts at the
    expected 4,096 x 4 x 8/64 = 2,048 entries, three products of 2048 x
    1536 each; the absent 56 experts and the full vocabulary are not
    counted."""
    cfg = tiny._load("configs", "glm-4.7-flash")
    c = spec.reference(cfg["reference"]).costs(cfg, 1)
    by = c["by_layer"]
    assert by["l2_moe_experts"] == 6 * 2048 * 3 * 2048 * 1536
    assert by["l2_moe_router"] == 6 * 4096 * 2048 * 64
    assert by["head"] == 6 * 4096 * 2048 * 19360
    assert c["experts"]["flops"] == 5 * by["l2_moe_experts"]
    assert c["attention"]["flops"] == 6 * by["l0_attn"]
    assert c["matmul"]["flops"] / 6 / 4096 == pytest.approx(328.99e6,
                                                            rel=1e-4)


def test_the_new_metrics_read_a_trace_or_nothing():
    """The four metrics this cell brings, on the hand-built trace of
    ``test_tracered``: a scope's time is a number (0 where no operation
    ran under it); the experts' roofline and the program's gauge have
    nothing to read where there is no such cost or gauge, and say so by
    returning nothing."""
    bench = spec.benchmark()
    ctx = {"trace": tracered.hand_trace(),
           "device": {"count": 2, "memory_peak_bytes": 7e9},
           "costs": {"model_flops": 1e6}, "peaks": spec.peaks("TPU v5 lite")}
    mine = {m["name"]: m for m in spec.Cell("glm47flash_train",
                                            bench).per_layer}
    def read(name):
        m = mine[name]
        return spec.reader(m["reader"]).read(ctx, **m.get("args", {}))
    assert read("ops.moe_route_ms.tok") == 0
    assert read("ops.mtp_ms.tok") == 0
    assert read("kernel.expert_roofline.tok") is None
    # the experts' time is what runs under their scope and the grouped
    # products' kernels, which arrive with a name and no scope
    ctx["trace"] = tracered.Trace(
        {0: [tracered.Op(0, 100, "l1_moe_experts_fwd_sort", tracered.OTHER,
                         "l1_moe_experts"),
             tracered.Op(100, 50, "ragged-dot-none", tracered.OTHER, ""),
             tracered.Op(150, 30, "l1_moe_shared_up_fwd_convolution",
                         tracered.DOT, "l1_moe_shared_up")]},
        [], (0, 200), steps=1)
    ctx["device"]["count"] = 1
    ctx["costs"]["experts"] = {"flops": 197e12 * 30e-9, "bytes": 1.0}
    assert read("kernel.expert_roofline.tok") == pytest.approx(
        100 * 30 / 150)
    from mxnet_tpu import obs
    obs.gauge("moe.load_max_over_mean").set(1.25)
    assert read("moe.load_max_over_mean.tok") == 1.25
    m = dict(mine["moe.load_max_over_mean.tok"])
    assert spec.reader(m["reader"]).read(ctx, name="moe.no_such") is None
    assert len(mine) == 14
