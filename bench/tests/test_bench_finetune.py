"""``correct`` of the finetuning driver at a size a test run holds: the
program's checked steps pass against the plain reference, the control
(the reference one precision below the configuration's) fails, and a
broken train step fails.  The harness's look for a chip is skipped."""

import os
import time

import pytest

from bench import harness

DATA = os.path.join(os.path.dirname(__file__), "data")
SPEC = {"end_to_end": [{"name": n, "unit": "u"} for n in
                       ("finetune_step_ms", "setup_s")], "per_layer": []}


def _run(seed, control=False):
    harness.import_program()
    return harness.Run(
        cell={"name": "tiny-ft", "chips": 1},
        cfg=harness.load_json(os.path.join(DATA, "tiny.json")),
        mix=harness.load_json(os.path.join(DATA, "tiny-ft.json")),
        seed=seed, seconds=0.5, trace=False, t_proc=time.perf_counter(),
        control=control)


def test_program_passes_control_and_half_batch_fail():
    result = harness.execute(_run(5, control=True), SPEC)
    checks, got = result["checks"], result["readings"]
    assert all(got[f"program_{k}"] <= c["limit"] for k, c in checks.items()
               if k != "window_compiles"), got
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in checks.values())
    assert any(got[f"half_batch_{k}"] > c["limit"] for k, c in checks.items()
               if k != "window_compiles")
    assert result["attempted"] >= 1 and result["failed"] == 0


def _unchanged(monkeypatch):
    import repro.runtime.trainer as trainer_mod
    make = trainer_mod.make_train_step

    def broken(*a, **k):
        step = make(*a, **k)

        def fn(state, batch):
            new, metrics = step(state, batch)
            return dict(state, step=new["step"]), metrics
        return fn
    monkeypatch.setattr(trainer_mod, "make_train_step", broken)


def _half_batch(monkeypatch):
    import repro.launch.steps as steps_mod
    loss = steps_mod.train_loss

    def half(params, adapters, batch, cfg, peft):
        n = batch["tokens"].shape[0] // 2
        return loss(params, adapters, {k: v[:n] for k, v in batch.items()},
                    cfg, peft)
    monkeypatch.setattr(steps_mod, "train_loss", half)


def _loss_altered(monkeypatch):
    import repro.runtime.trainer as trainer_mod
    make = trainer_mod.make_train_step

    def broken(*a, **k):
        step = make(*a, **k)

        def fn(state, batch):
            new, metrics = step(state, batch)
            return new, dict(metrics, loss=metrics["loss"] * 1.01)
        return fn
    monkeypatch.setattr(trainer_mod, "make_train_step", broken)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _loss_altered],
                         ids=["state_unchanged", "half_batch",
                              "loss_altered"])
def test_broken_train_step_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    assert harness.execute(_run(6), SPEC)["correct"] is False


def test_clean_finetune_is_correct():
    result = harness.execute(_run(6), SPEC)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"finetune_step_ms", "setup_s"}
