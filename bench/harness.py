"""The benchmark's harness: finds a cell's files by name, checks the
device, runs the driver for the cell's traffic kind, and prints the
result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration at its ``file``, its architecture at
``bench/archs/<arch>.py`` (the file's ``arch`` key, ``dense`` where it
has none), the traffic mix at ``bench/traffic/<traffic>.json``, the
driver at ``bench/drivers/<kind>.py`` (``kind`` is the mix's), and each
per-layer metric's reader at ``bench/metrics/<metric>.py``.  A later
cell adds files and entries; it edits none of these.

An architecture module supplies only what depends on its block; the
drivers, the readers and the shared code (``bench/model.py``,
``bench/system.py``, ``bench/counts.py``) reach it through
:func:`arch`.  Each function takes the configuration file's dict:

* ``dims(cfg)``: its sizes;
* ``projections(cfg)``: a list of ``model.Unit``, one per pattern
  position of the stack: where its leaves sit in the weight tree, its
  number of layers, and ``(d_in, d_out)`` of each projection by path
  (``mixer/q_proj``); adapters, the bank-GEMM counts and the readers
  are built from it;
* ``make_weights(cfg, seed)``: the weights in the program's tree, in
  one jitted call on the device;
* ``reference_hidden(cfg, weights, adapter, tokens, quant, remat)``:
  the plain float32 forward to the final hidden states (B, T, d), from
  the pieces in ``bench/model.py``; the reference programs put the
  output head on it;
* ``model_config(cfg)``: the program's ``ModelConfig``, refusing what
  the program cannot run;
* ``weight_bytes(cfg)``, ``decode_step(cfg, ctx_lens, tenants, bank)``,
  ``prefill(cfg, prompt_len, bank)``, ``train_step(cfg, batch, seq)``:
  the work counts, ``(flops, bytes)`` as ``bench/counts.py`` defines
  them.

A new architecture is a new module there and configuration files that
name it; it imports the pieces it shares and writes only its own block.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number compared for ``correct`` beside its limit.  The same checks
are the last lines of standard error.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import importlib.util
import json
import os
import sys
import tempfile
from typing import Any, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache: a fixed path inside the checkout,
# so only a checkout's first run of a cell compiles
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """One run of one cell, as the driver sees it."""
    cell: dict
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_proc: float                     # perf_counter at process start
    peak: Optional[dict] = None       # bench/peaks.json row of the device
    control: bool = False             # the control in the program's place


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""
    e2e: dict                         # end-to-end metric name -> value
    layer: Any                        # driver's record for the readers
    checks: dict                      # name -> (value, limit)
    attempted: int
    failed: int
    memory_peak: Optional[int] = None
    trace: Any = None                 # bench.trace.Trace of --trace 1
    readings: dict = dataclasses.field(default_factory=dict)


class Compiles:
    """Counts the process's XLA compilations (JAX's backend-compile
    event, which a load from the persistent cache records too), so a
    driver can show that nothing compiled inside its window."""
    n = 0
    _watching = False

    @classmethod
    def count(cls) -> int:
        if not cls._watching:
            from jax import monitoring

            def on_event(event, duration, **kwargs):
                if event == "/jax/core/compile/backend_compile_duration":
                    cls.n += 1
            monitoring.register_event_duration_secs_listener(on_event)
            cls._watching = True
        return cls.n


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_files(spec_: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of a cell, found by name."""
    cells = {w["name"]: w for w in spec_["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec_["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return cell, cfg, mix


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """The module in the file at ``path``, loaded once per process."""
    name = os.path.splitext(os.path.relpath(path, ROOT))[0]
    sp = importlib.util.spec_from_file_location(
        "bench_file_" + name.replace(os.sep, "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The per-layer metric's reader module, ``bench/metrics/<name>.py``."""
    return load_module(os.path.join(BENCH, "metrics", metric + ".py"))


def arch(cfg: dict):
    """The configuration's architecture module, ``bench/archs/<arch>.py``
    (the file's ``arch`` key, ``dense`` where it has none)."""
    name = cfg.get("arch", "dense")
    folder = os.path.join(BENCH, "archs")
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(folder)
                      if f.endswith(".py")) if os.path.isdir(folder) else []
        raise KeyError(f"{cfg.get('name')}: no architecture {name!r} in "
                       f"bench/archs (have {have})")
    return load_module(path)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(spec_: dict, cell: str, trace: bool) -> list[dict]:
    key = "per_layer" if trace else "end_to_end"
    return [m for m in spec_[key] if applies(m, cell)]


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def chips(n: int):
    """The devices to run on; raises :class:`NoChip` unless JAX sees at
    least ``n`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def import_program() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"the system under test is not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def execute(run: Run, spec_: dict) -> dict:
    """Run the cell's driver and build the result object."""
    out: Outcome = driver(run.mix["kind"]).run(run)
    metrics = {}
    for m in cell_metrics(spec_, run.cell["name"], run.trace):
        if run.trace:
            value = reader(m["name"]).read(out)
        else:
            value = out.e2e.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": all(v <= lim for v, lim in out.checks.values()),
              "attempted": int(out.attempted), "failed": int(out.failed),
              "metrics": metrics, "device": device_info(run, out)}
    if run.trace and out.trace is not None:
        from bench import trace as tr
        result["breakdown"] = {"device_ops": tr.top_ops(out.trace),
                               "idle_gaps": tr.idle_gaps(out.trace)}
    if out.readings:
        result["readings"] = out.readings
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    return result


def device_info(run: Run, out: Outcome) -> dict:
    import jax
    devs = jax.devices()[:run.cell["chips"]]
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": out.memory_peak}
    if run.trace and out.trace is not None:
        from bench import trace as tr
        info["busy_s"], info["window_s"] = tr.busy_per_device(out.trace)
    return info


def memory_peak(n: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the first ``n`` devices."""
    import jax
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()[:n]]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def trace_dir() -> str:
    return tempfile.mkdtemp(prefix="bench-trace-")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=str, default=None,
                    help="comma-separated seeds: on each, run the cell "
                         "with the control in the program's place, in "
                         "this process, and print one line per seed "
                         "(not a benchmark run)")
    return ap.parse_args(argv)


def main(argv, t_proc: float) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    spec_ = spec()
    cell, cfg, mix = cell_files(spec_, args.workload)
    try:
        devs = chips(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import_program()
    enable_cache()
    run = Run(cell=cell, cfg=cfg, mix=mix, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), t_proc=t_proc,
              peak=peaks(devs[0].device_kind))
    if args.control is not None:
        return control(run, spec_, [int(s) for s in args.control.split(",")])
    result = execute(run, spec_)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def control(run: Run, spec_: dict, seeds) -> int:
    """The control's readings (How ``correct`` is decided, step 2): on
    each seed the cell's own window, then the comparison with the
    control in the program's place; one line per seed with its
    ``correct``, the compared numbers and the program's beside them."""
    import gc
    import time
    for s in seeds:
        r = dataclasses.replace(run, seed=s, control=True,
                                t_proc=time.perf_counter(), trace=False)
        res = execute(r, spec_)
        line = {"seed": s, "correct": res["correct"],
                "checks": {k: c["value"] for k, c in res["checks"].items()},
                "readings": res.get("readings", {})}
        print("control " + json.dumps(line), flush=True)
        del res
        gc.collect()
    return 0
