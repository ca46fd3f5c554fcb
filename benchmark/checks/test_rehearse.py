"""A run of each cell at a tiny size on the CPU, the look for a chip
skipped; then the same run with the timed path broken underneath, which
has to come out as not correct; then the lower-precision control, which
has to as well.  Each tiny cell compares the numbers its cell's limits
file names, and no others.  No metric is printed."""
import time

import jax
import pytest

from lib import jobs, refsteps, spec, trainjob
import tiny

SEED = 2 ** 31 + 12345


def quiet(**facts):
    pass


def run(cell, seconds=0.3):
    return jobs.run(cell, SEED, seconds, False, time.time(), quiet)


def limits_from(result, factor=3.0):
    """Stand-in limits for a tiny size: three times a sound run's own
    readings (the cells' limits come from chip readings, PERF.md)."""
    return {k: factor * max(v["value"], 1e-6)
            for k, v in result["checked"].items()}


def a_sound_run(cell):
    """A tiny cell with its stand-in limits, the sound run they come
    from, and the reference's own first steps."""
    res = run(cell)
    return (tiny.with_limits(cell, limits_from(res)), res,
            reference_numbers(cell))


@pytest.fixture(scope="module")
def gpt_sound():
    return a_sound_run(tiny.gpt())


@pytest.fixture(scope="module")
def resnet_sound():
    return a_sound_run(tiny.resnet())


@pytest.fixture(params=["gpt", "resnet"])
def sound(request):
    return request.getfixturevalue(request.param + "_sound")


def test_one_chip_run_has_the_contracts_line(sound):
    cell, res, _ = sound
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checked" and res["correct"] is True
    assert set(res["metrics"]) == {cell.traffic["rate_metric"], "setup_s"}
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["checked"]) == set(cell.limits["limits"])


def test_no_job_of_an_unknown_kind():
    cell = tiny.gpt()
    cell.traffic["job"] = "serve"
    with pytest.raises(spec.SpecError, match="lib/servejob.py"):
        run(cell)


class Broken:
    """The fused step broken underneath the Module."""

    def __init__(self, monkeypatch, fault):
        from mxnet_tpu.parallel.trainer import Trainer
        real_step, real_batch = Trainer.step, Trainer._device_batch

        def step_unchanged(tr, batch, lr=None):
            keep = jax.tree.map(jax.numpy.copy,
                                (tr.params, tr.aux, tr.opt_state))
            out = real_step(tr, batch, lr)
            tr.params, tr.aux, tr.opt_state = keep
            return out

        def half_left_out(tr, batch):
            """Half of the rows stand in for all: the mean is taken
            over them alone."""
            dev = real_batch(tr, batch)
            out = {}
            for n, v in dev.items():
                k = v.shape[0] // 2
                tiled = jax.numpy.concatenate([v[:k]] * 2)
                out[n] = jax.device_put(tiled, v.sharding)
            return out

        if fault == "state_unchanged":
            monkeypatch.setattr(Trainer, "step", step_unchanged)
        else:
            monkeypatch.setattr(Trainer, "_device_batch", half_left_out)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(sound, monkeypatch, fault):
    cell, _, _ = sound
    Broken(monkeypatch, fault)
    res = run(cell)
    assert res["correct"] is False, res["checked"]


def reference_numbers(cell, **kw):
    init_key, data_key = jax.random.split(jax.random.key(SEED))
    cfg = cell.config
    batches = trainjob.make_batches(cfg["input"], cell.traffic["batch"],
                                    refsteps.STEPS, data_key)
    init_fn = jax.jit(lambda k: cell.reference.init(cfg, k))
    return trainjob.reference_steps(cell, init_fn, init_key, batches, **kw)


def judged(cell, got, want):
    numbers = refsteps.compare(got, want)
    return jobs.verdict(numbers, cell.limits["limits"])


def test_the_lower_precision_control_is_not_correct(gpt_sound):
    """The reference in float8 in the program's place fails one of the
    cell's own numbers, under limits that the program's bfloat16 holds;
    the reference itself in its place holds them all."""
    cell, _, want = gpt_sound
    rows = judged(cell, reference_numbers(cell, cast="fp8"), want)
    assert not all(held for _, _, _, held in rows), rows
    rows = judged(cell, reference_numbers(cell), want)
    assert all(held for _, _, _, held in rows), rows


def test_resnets_control_against_bfloat16_products(monkeypatch):
    """At a size the CPU holds the ResNet program's own gaps are as
    large as the control's (batch 16 at 128x128: 0.011 to 0.019 on the
    big leaves' median against 0.018 to 0.024): what tells them apart at
    the cell's size is 256 x 56 x 56 positions to average over, and
    ``readings.py`` shows that on the chip.  Here the reference with its
    products in bfloat16 stands in for the program: the control fails
    one of the cell's numbers under three times what that reads."""
    monkeypatch.setitem(
        refsteps.CASTS, "bf16",
        lambda x: x.astype(jax.numpy.bfloat16).astype(jax.numpy.float32))
    cell = tiny.resnet(batch=16, image=128)
    want = reference_numbers(cell)
    names = cell.limits["limits"]
    bf16 = refsteps.compare(reference_numbers(cell, cast="bf16"), want)
    cell = tiny.with_limits(cell, {k: 3.0 * bf16[k] for k in names})
    rows = judged(cell, reference_numbers(cell, cast="fp8"), want)
    assert not all(held for _, _, _, held in rows), rows


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_fault_planted_in_the_reference_is_not_correct(sound, fault):
    cell, _, want = sound
    rows = judged(cell, reference_numbers(cell, fault=fault), want)
    assert not all(held for _, _, _, held in rows), rows


def test_readings_put_program_control_and_fault_to_the_limits(gpt_sound):
    """``readings.py``'s loop at a tiny size: the program holds the
    cell's limits on every seed, the control and the fault fail one."""
    import readings
    cell, _, _ = gpt_sound
    lines = []
    assert readings.read(cell, [SEED, SEED + 1], 1, say=lines.append)
    assert lines[-3:] == [
        "program: 2 of 2 runs held every limit, as it has to",
        "control_fp8: 0 of 1 runs held every limit, as it has to",
        "fault_half_batch: 0 of 1 runs held every limit, as it has to"]
    loose = tiny.with_limits(cell, {k: 1.0 for k in cell.limits["limits"]})
    assert not readings.read(loose, [SEED], 1, say=lines.append)
    assert "WHICH IS WRONG" in lines[-1]
