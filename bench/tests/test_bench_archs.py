"""A configuration brings its own architecture with files alone: a
second architecture module and a configuration that names it, both
written outside ``bench/``, run through the unchanged harness, drivers
and reference.  The block is the dense one without rotary embeddings
(the program's ``rope_theta=None``); on the CPU the program passes
against its reference and the control fails, as in
``test_bench_correct.py`` and ``test_bench_finetune.py``."""

import hashlib
import json
import os
import time

import pytest

from bench import harness

DATA = os.path.join(harness.BENCH, "tests", "data")

NOPE = '''"""The dense block with no position embedding."""

import dataclasses
import os

import jax
import jax.numpy as jnp

from bench import harness, model

_dense = harness.load_module(
    os.path.join(harness.ROOT, "bench", "archs", "dense.py"))
projections = _dense.projections
make_weights = _dense.make_weights
weight_bytes = _dense.weight_bytes
decode_step = _dense.decode_step
prefill = _dense.prefill
train_step = _dense.train_step


def dims(cfg):
    return dict(_dense.dims(cfg), theta=None)


def model_config(cfg):
    return dataclasses.replace(_dense.model_config(cfg), rope_theta=None)


def reference_hidden(cfg, weights, adapter, tokens, quant=None,
                     remat=False):
    m = dims(cfg)
    f32 = jnp.float32
    T = tokens.shape[1]
    mask = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, xs):
        lw, la = xs
        h = model._rmsnorm(x, lw["norm1"]["scale"].astype(f32), m["eps"])
        x = x + model.attention(lw, la, h, None, mask, m["H"], m["Hkv"],
                                None, quant)
        h = model._rmsnorm(x, lw["norm2"]["scale"].astype(f32), m["eps"])
        return x + model.mlp(lw, la, h, m["glu"], quant), None

    if remat:
        layer = jax.checkpoint(layer)
    x = weights["embed"]["table"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, (weights["units"]["pos0"],
                                   adapter["units"]["pos0"]))
    return model._rmsnorm(x, weights["final_norm"]["scale"].astype(f32),
                          m["eps"])
'''


def _snapshot() -> dict:
    out = {}
    for folder, _, files in os.walk(harness.ROOT + os.sep + "bench"):
        for f in files:
            if f.endswith((".py", ".json")):
                with open(os.path.join(folder, f), "rb") as fh:
                    out[os.path.join(folder, f)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


@pytest.fixture
def nope(tmp_path, monkeypatch):
    """``tmp_path`` as the benchmark's folder, holding only the new
    architecture module; the configuration ``tiny.json`` naming it."""
    (tmp_path / "archs").mkdir()
    (tmp_path / "archs" / "nope.py").write_text(NOPE)
    cfg = dict(harness.load_json(os.path.join(DATA, "tiny.json")),
               name="tiny-nope", arch="nope",
               position_embedding_type="nope")
    path = tmp_path / "tiny-nope.json"
    path.write_text(json.dumps(cfg))
    monkeypatch.setattr(harness, "BENCH", str(tmp_path))
    return harness.load_json(str(path))


@pytest.mark.parametrize("mix,e2e", [("tiny-mix", "tok_s"),
                                     ("tiny-ft", "finetune_step_ms")])
def test_architecture_from_files_alone(nope, mix, e2e):
    before = _snapshot()
    harness.import_program()
    block = harness.arch(nope)
    assert os.path.dirname(block.__file__) == os.path.join(
        harness.BENCH, "archs")
    assert block.model_config(nope).rope_theta is None
    run = harness.Run(
        cell={"name": "tiny-nope", "chips": 1}, cfg=nope,
        mix=harness.load_json(os.path.join(DATA, mix + ".json")),
        seed=11, seconds=1.0 if mix == "tiny-mix" else 0.5, trace=False,
        t_proc=time.perf_counter(), control=True)
    spec = {"end_to_end": [{"name": n, "unit": "u"}
                           for n in (e2e, "setup_s")], "per_layer": []}
    result = harness.execute(run, spec)
    checks, got = result["checks"], result["readings"]
    program = {k: got[f"program_{k}"] for k in checks
               if f"program_{k}" in got}
    assert program and all(v <= checks[k]["limit"]
                           for k, v in program.items()), got
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in checks.values())
    assert set(result["metrics"]) == {e2e, "setup_s"}
    assert _snapshot() == before
