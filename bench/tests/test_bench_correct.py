"""``correct`` at a size a test run holds: the plain reference passes the
program's served tokens, fails the control (the reference one precision
below the configuration's, bf16 for this float32 test model, put in the
program's place), and fails runs whose timed path is broken underneath.
The harness's look for a chip is skipped: these call
``harness.execute`` on the CPU."""

import os
import time

import pytest

from bench import harness

DATA = os.path.join(os.path.dirname(__file__), "data")
SPEC = {"end_to_end": [{"name": n, "unit": "u"} for n in
                       ("tok_s", "setup_s")],
        "per_layer": []}


def _run(seed, control=False):
    harness.import_program()
    return harness.Run(
        cell={"name": "tiny", "chips": 1},
        cfg=harness.load_json(os.path.join(DATA, "tiny.json")),
        mix=harness.load_json(os.path.join(DATA, "tiny-mix.json")),
        seed=seed, seconds=1.0, trace=False, t_proc=time.perf_counter(),
        control=control)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_program_passes_and_control_fails(seed):
    result = harness.execute(_run(seed, control=True), SPEC)
    gap = result["checks"]["widest_gap"]
    assert result["readings"]["checked_tokens"] >= 100
    assert result["readings"]["program_widest_gap"] <= gap["limit"]
    assert gap["value"] > 3 * gap["limit"]
    assert result["correct"] is False


def _token_altered(monkeypatch):
    from repro.serving.engine import ServeEngine
    step = ServeEngine.step

    def altered(self):
        before = {s: len(r.tokens) for s, r in self.inflight().items()}
        done = step(self)
        for s, r in sorted(self.inflight().items()) or []:
            if len(r.tokens) > before.get(s, 0):
                r.tokens[-1] = (r.tokens[-1] + 1) % self.cfg.vocab
                break
        return done
    monkeypatch.setattr(ServeEngine, "step", altered)


def _state_unchanged(monkeypatch):
    from repro.models import api
    from repro.serving.engine import ServeEngine

    def unchanged(self, params, bank, state):
        cache = dict(state["cache"])
        logits, _ = api.decode_step(params, bank, cache, state["tok"],
                                    self.cfg, self.peft,
                                    tenant_ids=state["tenant"])
        _, nxt, bad = self._advance(state, logits, dict(cache))
        return state, nxt, bad
    monkeypatch.setattr(ServeEngine, "_step_impl", unchanged)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result = harness.execute(_run(4), SPEC)
    assert result["correct"] is False
    assert result["checks"]["widest_gap"]["value"] > \
        result["checks"]["widest_gap"]["limit"]


def test_clean_run_is_correct():
    result = harness.execute(_run(4), SPEC)
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"tok_s", "setup_s"}
