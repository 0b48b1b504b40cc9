"""Every cell's files are found by name, and the harness refuses to run
without a TPU."""

import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT


def test_benchmark_json_files_found_by_name():
    spec = harness.spec()
    for cell in spec["workloads"]:
        c, cfg, mix = harness.cell_files(spec, cell["name"])
        assert cfg["name"] == cell["config"]
        assert os.path.exists(os.path.join(harness.BENCH, "drivers",
                                           mix["kind"] + ".py"))
        assert hasattr(harness.driver(mix["kind"]), "run")
    for m in spec["per_layer"]:
        assert callable(harness.reader(m["name"]).read), m["name"]
    for conf in spec["configs"]:
        assert os.path.exists(os.path.join(ROOT, conf["file"]))


def test_metrics_per_cell():
    spec = harness.spec()
    e2e = {m["name"] for m in spec["end_to_end"]}
    for cell in spec["workloads"]:
        n = cell["name"]
        ends = {m["name"] for m in harness.cell_metrics(spec, n, False)}
        layers = harness.cell_metrics(spec, n, True)
        assert "setup_s" in ends and len(ends) >= 2 and layers
        for m in layers:
            assert m["moves"] in e2e and m["moves"] in ends


def test_reader_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "x.y.py").write_text(
        "def read(out):\n    return 42.0\n")
    monkeypatch.setattr(harness, "BENCH", str(tmp_path))
    assert harness.reader("x.y").read(None) == 42.0


def test_unknown_workload_and_device_kind():
    with pytest.raises(KeyError):
        harness.cell_files(harness.spec(), "no.such.cell")
    with pytest.raises(KeyError):
        harness.peaks("cpu")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi15.finetune",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "correct" not in p.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and "correct" not in p.stdout
