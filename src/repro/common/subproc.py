"""Fake-device subprocess helper shared by tests, benches, and CLI smokes.

jax locks the platform device count at first backend init, so any run
that needs N>1 fake CPU devices must set ``XLA_FLAGS`` *before* the
first ``import jax`` in a fresh process.  Two entry points:

- ``run_subprocess(code, devices=N)`` spawns a clean interpreter with
  ``--xla_force_host_platform_device_count=N`` and ``src`` on
  PYTHONPATH — the one way multi-device smokes run off-TPU (tests,
  ``benchmarks/serve_suite.py`` sharded rows, CI).
- ``set_host_device_count(n)`` is the in-process variant for scripts
  that own their interpreter (e.g. ``launch/dryrun.py``): it must be
  called before jax initializes and raises if it is too late.
"""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
SRC = os.path.join(_REPO, "src")

_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def run_subprocess(code, *, devices=1, timeout=300):
    """Run ``code`` in a fresh interpreter with ``devices`` fake CPU
    devices and return its stdout; raises AssertionError on failure.

    Raises RuntimeError when this process already holds a TPU: a chip
    belongs to one process at a time, and the fake-device child is a
    CPU-only construct anyway (it runs with ``JAX_PLATFORMS=cpu``)."""
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        if (xla_bridge.backends_are_initialized()
                and sys.modules["jax"].default_backend() == "tpu"):
            raise RuntimeError(
                "run_subprocess: this process holds the TPU; a child "
                "process cannot share the chip — run the multi-device "
                "path in-process instead")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = f"{_DEVICE_FLAG}={int(devices)}"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise AssertionError(f"subprocess failed:\nSTDOUT:\n{out.stdout}"
                             f"\nSTDERR:\n{out.stderr}")
    return out.stdout


def set_host_device_count(n):
    """Force ``n`` fake CPU devices for this process.

    Must run before jax's backend initializes (i.e. before anything
    imports jax and touches devices) — raises RuntimeError if jax has
    already locked the device count.
    """
    if "jax" in sys.modules:
        import jax
        # backend already materialized with a different count? too late.
        if jax._src.xla_bridge._backends and len(jax.devices()) != n:
            raise RuntimeError(
                "set_host_device_count must be called before jax "
                "initializes its backend")
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if f and not f.startswith(_DEVICE_FLAG + "=")]
    flags.append(f"{_DEVICE_FLAG}={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
