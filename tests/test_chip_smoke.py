"""chip_smoke.py's phases at smoke widths on the CPU.

The script itself refuses to run without a TPU; its phase functions are
the same code at any width, so here they run at ``--variant smoke`` with
interpret-mode kernels and a shrunken traffic shape, checking their own
assertions (completion, zero failure accounting, no retrace, kernel
counters, logits against the float32 reference).
"""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name, value in dict(BUCKETS=(16, 32), GEN=4, N_REQUESTS=12,
                            CAPACITY=4, UNIVERSE=8, TRAIN_BATCH=2,
                            TRAIN_SEQ=32, TRAIN_STEPS=2).items():
        monkeypatch.setattr(mod, name, value)
    return mod


def test_serve_phase_smoke(smoke):
    out = smoke.serve_phase("smoke", seed=0)
    assert out["promotions"] >= 1
    assert max(out["logits_rel_l2"]) <= smoke.LOGITS_RTOL
    assert all(k.endswith((".pallas", ".pallas_rows"))
               for k in out["counters"])
    # the bank decode step ran the folded grid of the bank GEMM
    assert out["counters"]["householder_gemm_batched.pallas_rows"] >= 1
    json.dumps(out, default=str)                # printable as a phase line


def test_train_phase_smoke(smoke):
    out = smoke.train_phase("smoke", seed=0)
    assert out["fwd"] and out["bwd"]
    assert all(k.endswith(".pallas") for k in {**out["fwd"], **out["bwd"]})


def test_sharded_phase_smoke_single_device(smoke):
    out = smoke.sharded_phase("smoke", seed=0, shape=(1, 1))
    assert out["same_tokens"] == smoke.N_REQUESTS
    assert max(out["logits_rel_l2"]) == 0.0     # same mesh, same program


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_run_subprocess_refuses_from_a_tpu_process(monkeypatch):
    """A child cannot get the chip its parent holds: the fake-device
    subprocess helper raises instead of starting it."""
    import jax
    from repro.common import subproc
    jax.devices()                               # backends initialized
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        subproc.run_subprocess("print('never runs')")
