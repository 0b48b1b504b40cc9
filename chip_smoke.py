"""Bring-up check of the multi-tenant ETHER serve and finetune path on a
TPU, at Phi-1.5 widths (d_model 2048, d_ff 8192, 24 layers, random
weights from ``--seed``).

    python chip_smoke.py              # serve phase + train phase, one chip
    python chip_smoke.py --chips 4    # sharded serve engine on a 2x2 mesh
                                      # against the same engine on 1x1

Serve phase: ``ServeEngine`` / ``AdapterRegistry`` / ``Scheduler`` (the
objects ``launch/serve.py --trace`` builds) replay 24 Zipf-skewed
requests over a 32-tenant universe through a 16-row ETHER bank (n=32
blocks, ``backend="auto"``), 8 slots, prompt buckets 128/512, 32 new
tokens each, with a one-entry merged hot tier.  Required: every request
completes, all failure accounting is zero, nothing retraces after
warmup, at least one tenant is promoted, every traced adapter op ran
its Pallas kernel, and the prefill logits of three requests are within
``LOGITS_RTOL`` of a float32 ``backend="jnp"`` reference on the same
weights.

Train phase: ``repro.launch.train.run`` takes five ETHER ``Trainer``
steps (batch 8, seq 512, ``--backend auto``); the loss must be finite
and every forward and backward adapter op must have run its kernel.

The script refuses to run unless JAX's first device is a TPU, prints
its readings as ``phase {json}`` lines, and ends with one JSON line
``{"ok": true, "device": {...}}``.  The readings are bring-up numbers,
not benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ARCH, METHOD, N_BLOCKS = "phi-1.5", "ether", 32
SLOTS, BUCKETS, GEN = 8, (128, 512), 32
CAPACITY, UNIVERSE, N_REQUESTS, ZIPF_A = 16, 32, 24, 1.5
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 5, 8, 512
# Relative L2 distance between last-position prefill logits of the
# bf16 kernel path and a float32 jnp reference on the same weights.
# bf16 activations alone put the full 24-layer Phi-1.5 at 0.0063 on the
# CPU (128- and 512-token prompts), while another tenant's adapter
# moves the logits by 0.14 there: the check must sit between the two.
LOGITS_RTOL = 0.04
N_LOGIT_CHECKS = 3


class SmokeFailure(AssertionError):
    """A phase ran but its output is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_l2(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check_kernels(counters: dict, what: str) -> None:
    """Every traced adapter op must have run its Pallas kernel: no
    ``.jnp`` fallback, no ``.pallas_fallback``."""
    live = {k: v for k, v in counters.items() if v}
    check(bool(live), f"{what}: no adapter op was traced")
    bad = sorted(k for k in live if not k.endswith(".pallas"))
    check(not bad, f"{what}: ops off the kernel path: {bad}")


def _setup(variant: str, seed: int):
    import jax
    from repro.configs import get_config, peft_targets
    from repro.core.transforms import PEFTConfig
    from repro.models import init_model
    cfg = get_config(ARCH, variant)
    peft = PEFTConfig(method=METHOD, n_blocks=N_BLOCKS,
                      targets=peft_targets(ARCH), backend="auto")
    return cfg, peft, init_model(jax.random.PRNGKey(seed), cfg)


def _replay(cfg, peft, params, seed: int, mesh=None) -> dict:
    """One checked replay through the engine; returns readings plus the
    live objects under ``_engine`` / ``_registry`` / ``_done``."""
    import jax
    from repro.serving import (AdapterRegistry, Scheduler, ServeEngine,
                               summarize, synthetic_workload)
    registry = AdapterRegistry(params, peft, CAPACITY, n_tenants=UNIVERSE,
                               rng=jax.random.fold_in(
                                   jax.random.PRNGKey(seed), 1),
                               merged_capacity=1)
    engine = ServeEngine(cfg, params, registry, peft, slots=SLOTS,
                         prompt_buckets=BUCKETS, max_new_tokens=GEN,
                         mesh=mesh)
    t0 = time.perf_counter()
    snap = engine.warmup()
    compile_s = time.perf_counter() - t0
    workload = synthetic_workload(
        N_REQUESTS, UNIVERSE, vocab=cfg.vocab, zipf_a=ZIPF_A,
        prompt_lens=(4, BUCKETS[-1]), gen_lens=(GEN, GEN), seed=seed)
    sched = Scheduler(engine)
    done = sched.run(workload)
    engine.assert_no_retrace(snap)
    acc = sched.accounting()
    check(len(done) == N_REQUESTS,
          f"{len(done)}/{N_REQUESTS} requests completed")
    check(all(len(r.tokens) == GEN for r in done), "short generations")
    errors = [str(r.error) for r in sched.failed + sched.dropped][:3]
    check(not any(acc.values()), f"failure accounting {acc}: {errors}")
    check(not any(engine.fault_stats.values()),
          f"engine faults {engine.fault_stats}")
    check(registry.stats["merge_failures"] == 0,
          f"{registry.stats['merge_failures']} merge failures")
    check(registry.stats["promotions"] >= 1, "no tenant was promoted")
    s = summarize(done, scheduler=sched)
    return dict(
        compile_s=compile_s, tok_s=s["throughput_tok_s"],
        ttft_p50_ms=s["ttft_p50_ms"], ttft_p95_ms=s["ttft_p95_ms"],
        p50_ms_per_token=s["p50_ms_per_token"],
        promotions=registry.stats["promotions"],
        evictions=registry.stats["evictions"],
        merged_tokens=engine.tier_stats["merged_tokens"],
        _engine=engine, _registry=registry, _done=done)


def _prefill_logits_fn(cfg, peft, params, mesh=None):
    """Last-real-position prefill logits of one request through a
    one-tenant bank, right-padded to its engine bucket like the engine's
    own prefill: ``fn(adapters, prompt) -> (vocab,) f32``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.peft import AdapterBank
    from repro.models import api
    from repro.parallel.context import MeshContext, mesh_context

    @jax.jit
    def logits(p, bank, tokens, n):
        return api.prefill(p, bank, {"tokens": tokens}, cfg, peft,
                           tenant_ids=jnp.zeros((1,), jnp.int32),
                           true_lens=n)[1][0, -1]

    def fn(adapters, prompt):
        bucket = next(b for b in BUCKETS if len(prompt) <= b)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(prompt)] = prompt
        args = (params, AdapterBank.stack([adapters], params, peft), tokens,
                np.asarray([len(prompt)], np.int32))
        if mesh is None:
            return np.asarray(logits(*args), np.float32)
        with mesh_context(MeshContext(mesh, seq_shard=False)):
            return np.asarray(logits(*args), np.float32)
    return fn


def _picked(done):
    """The requests whose prefill logits are checked: prefer the 512
    bucket so the check compiles one prefill shape."""
    return sorted(done, key=lambda r: len(r.prompt) <= BUCKETS[0]
                  )[:N_LOGIT_CHECKS]


def _peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def serve_phase(variant: str = "full", seed: int = 0) -> dict:
    """Replay through the engine, then check prefill logits against the
    float32 jnp reference.  Returns the phase's readings."""
    import jax
    from repro.core import execute
    cfg, peft, params = _setup(variant, seed)
    execute.reset_counters()
    out = _replay(cfg, peft, params, seed)
    registry, picked = out.pop("_registry"), _picked(out.pop("_done"))
    out.pop("_engine")
    adapters = [registry.adapters_for(r.tenant_id) for r in picked]
    kernel = _prefill_logits_fn(cfg, peft, params)
    got = [kernel(a, r.prompt) for a, r in zip(adapters, picked)]
    out["counters"] = execute.counters()
    check_kernels(out["counters"], "serve")
    # the engine's greedy first token against the kernel-path prefill
    out["first_token_matches"] = sum(
        int(g.argmax()) == r.tokens[0] for g, r in zip(got, picked))
    # the reference: float32 compute on the same bf16 weights, plain jnp
    # adapter ops, full-precision matmuls
    ref = _prefill_logits_fn(dataclasses.replace(cfg, compute_dtype="float32"),
                             dataclasses.replace(peft, backend="jnp"), params)
    with jax.default_matmul_precision("highest"):
        want = [ref(a, r.prompt) for a, r in zip(adapters, picked)]
        other = (picked[0].tenant_id + 1) % UNIVERSE
        wrong = ref(registry.adapters_for(other), picked[0].prompt)
    errs = [rel_l2(g, w) for g, w in zip(got, want)]
    out["logits_rel_l2"] = errs
    out["tenant_separation_rel_l2"] = rel_l2(wrong, want[0])
    check(all(e <= LOGITS_RTOL for e in errs),
          f"prefill logits off the float32 reference: {errs} > "
          f"{LOGITS_RTOL}")
    check(out["tenant_separation_rel_l2"] > 2 * LOGITS_RTOL,
          "another tenant's adapter lands within the tolerance: the "
          "logits check cannot tell tenants apart")
    out["peak_bytes_in_use"] = _peak_bytes()
    return out


def train_phase(variant: str = "full", seed: int = 0) -> dict:
    """Five Trainer steps through the training CLI's ``run``."""
    from repro.core import execute
    from repro.launch import train
    execute.reset_counters()
    args = train.build_argparser().parse_args([
        "--arch", ARCH, "--variant", variant, "--method", METHOD,
        "--n-blocks", str(N_BLOCKS), "--steps", str(TRAIN_STEPS),
        "--batch", str(TRAIN_BATCH), "--seq-len", str(TRAIN_SEQ),
        "--backend", "auto", "--seed", str(seed)])
    t0 = time.perf_counter()
    metrics = train.run(args)
    wall = time.perf_counter() - t0
    check(metrics.get("step") == TRAIN_STEPS, f"stopped at {metrics}")
    check("loss" in metrics and math.isfinite(metrics["loss"]),
          f"loss not finite: {metrics}")
    check_kernels(execute.counters("fwd"), "train forward")
    check_kernels(execute.counters("bwd"), "train backward")
    return dict(loss=metrics["loss"], wall_s=wall,
                last_step_s=metrics["step_time"],
                tok_s=TRAIN_BATCH * TRAIN_SEQ / metrics["step_time"],
                fwd=execute.counters("fwd"), bwd=execute.counters("bwd"),
                peak_bytes_in_use=_peak_bytes())


def sharded_phase(variant: str = "full", seed: int = 0,
                  shape: tuple[int, int] = (2, 2)) -> dict:
    """The sharded serve engine (DESIGN.md §14) on a dp×tp mesh against
    the same engine on a 1×1 mesh: both replays checked, then prefill
    logits of three requests compared within ``LOGITS_RTOL``."""
    from repro.core import execute
    from repro.launch.mesh import make_host_mesh
    cfg, peft, params = _setup(variant, seed)
    runs, logits, tokens = {}, {}, {}
    for role, (dp, tp) in (("base", (1, 1)), ("grid", shape)):
        mesh = make_host_mesh(dp, tp)
        execute.reset_counters()
        run = _replay(cfg, peft, params, seed, mesh=mesh)
        engine, registry = run.pop("_engine"), run.pop("_registry")
        done = sorted(run.pop("_done"), key=lambda r: r.rid)
        fn = _prefill_logits_fn(cfg, peft, engine.params, mesh=mesh)
        logits[role] = [fn(registry.adapters_for(r.tenant_id), r.prompt)
                        for r in _picked(done)]
        tokens[role] = {r.rid: r.tokens for r in done}
        runs[role] = dict(run, mesh=[dp, tp], replicas=engine.n_replicas,
                          counters=execute.counters())
        del engine, registry, fn
        gc.collect()
    errs = [rel_l2(g, w) for g, w in zip(logits["grid"], logits["base"])]
    out = dict(runs, logits_rel_l2=errs, same_tokens=sum(
        tokens["base"][rid] == toks for rid, toks in tokens["grid"].items()))
    check(all(e <= LOGITS_RTOL for e in errs),
          f"{shape} mesh logits off the 1x1 mesh: {errs} > {LOGITS_RTOL}")
    out["peak_bytes_in_use"] = _peak_bytes()
    return out


def _import_repro() -> None:
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit("chip_smoke: src/repro not found next to this "
                         "script; run it from a checkout of the repo")
    sys.path.insert(0, src)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded serve engine on a 2x2 "
                         "mesh against a 1x1 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _import_repro()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    from repro.common import compile_cache
    compile_cache.enable()
    phases = ([("sharded", sharded_phase)] if args.chips == 4 else
              [("serve", serve_phase), ("train", train_phase)])
    for name, fn in phases:
        readings = fn("full", args.seed)
        print(name, json.dumps(readings, default=str), flush=True)
        gc.collect()                 # the next phase builds its own model
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
