"""Every cell's files are found by name, and the harness refuses to run
without a TPU."""

import glob
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness, model

ROOT = harness.ROOT
# what an architecture module supplies (bench/harness.py)
INTERFACE = ("dims", "projections", "make_weights", "reference_hidden",
             "model_config", "weight_bytes", "decode_step", "prefill",
             "train_step")


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


def _config_files():
    spec = harness.spec()
    files = [os.path.join(ROOT, c["file"]) for c in spec["configs"]]
    files += [os.path.join(harness.BENCH, "tests", "data", n + ".json")
              for n in ("tiny", "scopes")]
    return files


@pytest.mark.parametrize("path", _config_files(),
                         ids=lambda p: os.path.basename(p))
def test_every_configuration_has_its_architecture(path):
    cfg = harness.load_json(path)
    block = harness.arch(cfg)
    assert all(callable(getattr(block, f, None)) for f in INTERFACE)
    units = block.projections(cfg)
    assert units and all(isinstance(u, model.Unit) for u in units)
    assert sum(u.count for u in units) == cfg["num_hidden_layers"]
    assert all(model.targets(cfg, u.shapes) for u in units)


def test_unknown_architecture_names_what_exists():
    with pytest.raises(KeyError, match=r"no architecture 'no-such'.*dense"):
        harness.arch({"name": "x", "arch": "no-such"})


def test_only_the_lookup_names_an_architecture():
    """The drivers, the readers, the shared code and the trace recorder
    reach the block only through ``harness.arch``."""
    bench = harness.BENCH
    files = (glob.glob(os.path.join(bench, "*.py"))
             + glob.glob(os.path.join(bench, "drivers", "*.py"))
             + glob.glob(os.path.join(bench, "metrics", "*.py"))
             + [os.path.join(bench, "tests", "record_scopes.py")])
    named = re.compile(r"archs(\.|/|\s+import\s+)dense"
                       r"|[\"']dense(\.py)?[\"']")
    for path in files:
        if os.path.basename(path) == "harness.py":
            continue
        with open(path) as f:
            assert not named.search(f.read()), path


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
