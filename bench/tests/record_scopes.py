"""Record ``bench/tests/data/tpu-scopes.xplane.pb``: a small trace of the
program's own spans and scopes on a TPU, read by ``test_bench_scopes.py``.

    python3 bench/tests/record_scopes.py <out.xplane.pb>

A two-layer ETHER model at d 256 (``data/scopes.json``, weights from
its architecture module, adapters from ``bench/model.py``) admits one
request into a ``ServeEngine`` (its tenant not yet in the bank, so the
registry swaps it in) and takes three bank decode steps, then a
``Trainer`` takes two ``fit`` steps under ``remat=full``.  Everything
compiles first; the profiler then records the six calls, 20 ms apart,
inside the benchmark's own spans as the drivers open them
(``bench.window``, ``bench.admit``, ``bench.step`` with ``n``), which
align the device's clock.  The file kept is the trace cut to what
``bench/trace.py`` reads (:func:`trim`).  Exits 2 without a TPU.
"""

import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT

SEED = 20261018
PAUSE_S = 0.02
TENANT = 3
PROMPT, BUCKET, GEN = 40, 64, 8
BATCH, SEQ = 2, 64


# what bench/trace.py reads of a trace, and bench/xplane.py of its
# operations' metadata
DEVICE_LINES = ("XLA Ops", "XLA Modules")
SPAN_PREFIXES = ("bench.", "engine.", "registry.", "trainer.")
KEEP_STATS = ("tf_op", "program_id")
NAME_CHARS = 120


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, bytes):
        return _varint(num << 3 | 2) + _varint(len(value)) + value
    return _varint(num << 3) + _varint(value)


def _message(fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def trim(raw: bytes) -> bytes:
    """An XSpace cut to what the benchmark reads: on a device plane the
    ``XLA Ops`` and ``XLA Modules`` lines (events without their own
    stats) and of each event's metadata its id, its name's first
    ``NAME_CHARS`` characters and its ``tf_op`` and ``program_id``; on a
    host plane the events of the spans the reader loads; no other plane
    (``/host:metadata`` holds every program's HLO).  Event times and the
    names the reader matches are kept whole."""
    from bench.xplane import _fields, _map_entry, _text
    planes = []
    for num, plane in _fields(raw):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for n, v in fields if n == 2), "")
        device = name.startswith("/device:TPU:")
        if not (device or name == "/host:CPU"):
            continue
        stat_names = {}
        for n, v in fields:
            if n == 5:
                k, m = _map_entry(v)
                stat_names[k] = _text(dict(_fields(m)).get(2, b""))
        meta_names = {}
        out = []
        for n, v in fields:
            if n == 4:
                k, m = _map_entry(v)
                mf = list(_fields(m))
                meta_names[k] = next((_text(x) for f, x in mf if f == 2), "")
                if device:
                    m = _message(
                        [(f, x[:NAME_CHARS] if f == 2 else x) for f, x in mf
                         if f in (1, 2) or f == 5 and stat_names.get(
                             dict(_fields(x)).get(1)) in KEEP_STATS])
                out.append((n, _message([(1, k), (2, m)])))
            elif n != 3:
                out.append((n, v))
        for n, v in fields:
            if n != 3:
                continue
            lf = list(_fields(v))
            lname = next((_text(x) for f, x in lf if f == 2), "")
            if device and lname not in DEVICE_LINES:
                continue
            kept = []
            for f, x in lf:
                if f != 4:
                    kept.append((f, x))
                    continue
                ev = list(_fields(x))
                mid = next((e for g, e in ev if g == 1), 0)
                if device:
                    kept.append((f, _message([(g, e) for g, e in ev
                                              if g != 4])))
                elif meta_names.get(mid, "").startswith(SPAN_PREFIXES):
                    kept.append((f, x))
            if any(f == 4 for f, _ in kept):
                out.append((n, _message(kept)))
        planes.append(_message(out))
    return b"".join(_field(1, p) for p in planes)


def record(out_path: str) -> int:
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench import harness, model, system
    from bench.drivers.finetune import Stream
    harness.import_program()
    from repro.optim import adamw, constant
    from repro.runtime.trainer import Trainer
    from repro.serving import AdapterRegistry, ServeEngine
    from repro.serving.scheduler import Request

    cfg = harness.load_json(os.path.join(ROOT, "bench", "tests", "data",
                                         "scopes.json"))
    sv = cfg["serving"]
    block = harness.arch(cfg)
    mcfg, peft = block.model_config(cfg), system.peft_config(cfg)
    weights = block.make_weights(cfg, SEED)
    registry = AdapterRegistry(weights, peft, sv["bank_rows"],
                               n_tenants=sv["tenants"],
                               init_fn=model.adapter_fn(cfg, SEED))
    engine = ServeEngine(mcfg, weights, registry, peft, slots=sv["slots"],
                         prompt_buckets=(BUCKET,), max_new_tokens=GEN,
                         max_len=sv["max_len"])
    engine.warmup()
    trainer = Trainer(mcfg, peft, adamw(constant(2e-3)), restore="none")
    stream = Stream(cfg["vocab_size"], BATCH, SEQ, SEED)
    trainer.fit(stream, steps=1)                 # compiles the step
    prompt = np.random.default_rng(SEED).integers(
        0, cfg["vocab_size"], PROMPT, dtype=np.int32)
    req = Request(rid=0, tenant_id=TENANT, prompt=prompt,
                  max_new_tokens=GEN, arrival_s=0.0)

    # the calls take a few milliseconds each, about the device clock's
    # error: a pause between them leaves the clock alignment one fit
    # (back to back, a shift by one call fits as many programs)
    calls = [("bench.admit", {}, lambda: engine.admit(req))]
    calls += [("bench.step", {"n": n}, engine.step) for n in range(3)]
    calls += [("bench.step", {"n": n},
               lambda: trainer.fit(stream, steps=trainer.step + 1))
              for n in range(3, 5)]
    log_dir = tempfile.mkdtemp(prefix="scopes-trace-")
    jax.profiler.start_trace(log_dir)
    with TraceAnnotation("bench.window"):
        for name, stats, call in calls:
            time.sleep(PAUSE_S)
            with TraceAnnotation(name, **stats):
                call()
    jax.profiler.stop_trace()
    from bench import trace as tr
    with open(tr.find_xplane(log_dir), "rb") as f:
        raw = f.read()
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "wb") as f:
        f.write(trim(raw))
    print(f"{out_path}: {os.path.getsize(out_path)} bytes, "
          f"{len(req.tokens)} tokens served")
    return 0


def main(argv) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    from bench import harness
    try:
        harness.chips(1)
    except harness.NoChip as e:
        print(f"record_scopes: {e}", file=sys.stderr)
        return 2
    return record(argv[0])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
