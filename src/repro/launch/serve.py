"""Serving CLI — thin frontend over the continuous-batching engine.

One-shot latency modes (static batch, fixed tenants):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --variant smoke --batch 4 --prompt-len 32 --gen 16

* ``--merged``: absorb adapters into the base weights (paper's
  zero-latency deployment, core.merge_params) and serve the plain model;
* default: unmerged activation-side adapters — per-step reflections on
  the frozen weights;
* ``--tenants N``: static multi-tenant comparison (DESIGN.md §2): an
  N-tenant :class:`~repro.core.peft.AdapterBank` serving the batch
  unmerged vs the tenant-0 merged baseline.

Greedy sampling runs INSIDE the jitted prefill/step functions, so the
reported ms/token is device work — host bookkeeping (output collection)
stays out of the timed loop.

Continuous-batching replay (the real serving subsystem, DESIGN.md §9):

    PYTHONPATH=src python -m repro.launch.serve --trace --tenants 64 \
        --backend auto

``--trace`` replays a synthetic Poisson/Zipf workload through
``repro.serving``: ``--tenants`` is the device bank *capacity*; the
tenant universe (``--distinct-tenants``, default 4×capacity) exceeds it,
so cold tenants are onboarded (functional bank-row swaps) and LRU
tenants evicted mid-traffic.  Requests are admitted into free decode
slots and retired as they finish — with zero recompiles after warmup,
asserted via the engine's jit-cache-miss counter.  Reports throughput,
p50/p95 per-token latency, time-to-first-token, registry churn, and
admission-rejected (dropped) requests — one malformed request in a
trace is counted and shed, never a replay abort.  With
``--merged-capacity N`` the registry runs the two-tier policy
(DESIGN.md §11): hot tenants are promoted into an N-entry merged-weight
cache and served reflection-free; the report adds the hot-tier token
hit rate, promotion/demotion/eviction counts, and merge time.

``--deadline-ms`` stamps per-request SLOs (TTFT = half the budget;
blown-TTFT requests are shed before prefill, blown-total cancelled by
the watchdog) and the report adds SLO-attainment columns.
``--chaos-seed`` replays the same trace under a seeded
:class:`~repro.serving.FaultPlan` drawing from every fault class —
corrupted adapters, kernel raises, merge failures, stragglers, eviction
storms (DESIGN.md §12) — and the report adds the split failure
accounting plus typed outcome counts.  Degradation is bookkeeping:
zero recompiles is asserted in both modes.

``--journal-dir`` makes the replay crash-safe (DESIGN.md §13): adapter
puts spill through a durable atomic-rename store and every admission /
token / outcome is written ahead to an append-only journal
(``--fsync-every`` batches the fsyncs).  ``--kill-at-step N`` SIGKILLs
the process at the Nth engine step (exit 137); rerunning the same
command with ``--restore`` instead warm-restarts it: registry
membership is rebuilt from the journal, in-flight requests resume as
extended prefills, the not-yet-journaled remainder replays, and the
report asserts every rid landed in exactly one accounting bucket and
prints the measured restart RTO.

All four decoder families serve through the engine: attention models
via causal pad masking, Mamba-2 (``--arch mamba2-1.3b``) and
RecurrentGemma (``--arch recurrentgemma-9b``) via pad-invariant
recurrent prefill — pad positions are identity state updates, so the
per-slot SSM/RG-LRU state equals the unpadded prompt's (DESIGN.md
§10).  For windowed-attention hybrids keep the largest bucket + --gen
within ``cfg.window`` (ring wrap is rejected at engine construction).

``--method`` / ``--backend {jnp,pallas,auto}`` select the PEFT method
(any name in ``repro.core.methods.available()`` — ETHER variants,
DeLoRA, HyperAdapt, ...) and execution backend (core.execute) in every
mode; bank/trace modes additionally require a bank-servable method
(``repro.core.methods.bank_servable()``).
"""

from __future__ import annotations

import argparse
import time


def make_serving_fns(cfg, peft_cfg, gen: int):
    """Jitted (prefill, step) with greedy sampling fused inside: the
    step returns the next token, not logits, so timing the step times
    device work only (argmax/bookkeeping included in the jit).

    The prefill grows the cache to prompt + ``gen`` + 1 positions
    (``pad_cache``) so decode writes land past the prompt instead of
    clamping onto its last position — the pre-engine driver skipped
    this and silently clobbered the final prompt token's KV."""
    import jax
    import jax.numpy as jnp
    from repro.models import decode_step, prefill
    from repro.models.api import pad_cache

    @jax.jit
    def pf(params, adapters, batch, ids):
        cache, logits = prefill(params, adapters, batch, cfg, peft_cfg,
                                tenant_ids=ids)
        cache = pad_cache(cache, cfg,
                          batch["tokens"].shape[1] + gen + 1)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return cache, tok

    @jax.jit
    def st(params, adapters, cache, tok, ids):
        logits, new_cache = decode_step(params, adapters, cache, tok, cfg,
                                        peft_cfg, tenant_ids=ids)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, new_cache

    return pf, st


def _timed_generation(pf, st, params, adapters, batch, gen,
                      tenant_ids=None):
    """Run prefill + ``gen`` greedy decode steps; returns
    (t_prefill_s, t_per_token_s, generated (B, gen+1)).

    Warms up (compiles) both entry points before timing so the reported
    numbers compare serving latency, not XLA compile time."""
    import jax
    import jax.numpy as jnp

    cache, tok = pf(params, adapters, batch, tenant_ids)
    t2, _ = st(params, adapters, cache, tok, tenant_ids)
    jax.block_until_ready(t2)

    t0 = time.perf_counter()
    cache, tok = pf(params, adapters, batch, tenant_ids)
    tok.block_until_ready()
    t_prefill = time.perf_counter() - t0

    out_tokens = [tok]
    t0 = time.perf_counter()
    for _ in range(gen):
        tok, cache = st(params, adapters, cache, tok, tenant_ids)
        out_tokens.append(tok)
    tok.block_until_ready()
    t_gen = time.perf_counter() - t0
    return (t_prefill, t_gen / max(gen, 1),
            jnp.concatenate(out_tokens, axis=1))


def run_trace(args, cfg, peft, params, rng):
    """Continuous-batching replay over the serve engine."""
    import dataclasses
    import os

    import jax
    from repro.core.peft import validate_tenant_ids
    from repro.serving import (AdapterRegistry, AdapterStore, FaultPlan,
                               Journal, Scheduler, ServeEngine, recover,
                               summarize, synthetic_workload)

    capacity = args.tenants if args.tenants > 0 else 8
    distinct = args.distinct_tenants or 4 * capacity
    n_req = args.requests or 3 * capacity
    buckets = tuple(int(b) for b in args.prompt_buckets.split(","))

    faults = None
    if args.chaos_seed is not None:
        # seeded chaos replay (DESIGN.md §12): injected faults from every
        # class; the replay must complete with typed per-request outcomes
        faults = FaultPlan.sample(args.chaos_seed,
                                  n_steps=max(16, n_req * args.gen
                                              // max(args.slots, 1)),
                                  tenants=distinct)
    if args.kill_at_step is not None:
        # scheduled process death for the kill-and-restore drill: a REAL
        # SIGKILL at the Nth engine step (exit 137) — the restarted
        # process recovers with --restore over the same --journal-dir
        crash = {"step": int(args.kill_at_step)}
        faults = (FaultPlan(crash_at=crash, crash_kill=True)
                  if faults is None else
                  dataclasses.replace(faults, crash_at=crash,
                                      crash_kill=True))
    store = journal = None
    if args.journal_dir:
        store = AdapterStore(os.path.join(args.journal_dir, "adapters"),
                             method=args.method, faults=faults)
        journal = Journal(os.path.join(args.journal_dir, "journal.jsonl"),
                          fsync_every=args.fsync_every, faults=faults)
    elif args.restore:
        raise SystemExit("--restore requires --journal-dir (the journal "
                         "and durable store of the dead process)")
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh
        try:
            dp, tp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            raise SystemExit(f"--mesh wants dp,tp (got {args.mesh!r})")
        if dp * tp > len(jax.devices()):
            raise SystemExit(f"--mesh {dp}x{tp} needs {dp * tp} devices, "
                             f"have {len(jax.devices())} (use "
                             f"--fake-devices off-TPU)")
        mesh = make_host_mesh(dp, tp)
    registry = AdapterRegistry(params, peft, capacity, n_tenants=distinct,
                               rng=jax.random.fold_in(rng, 1),
                               merged_capacity=args.merged_capacity,
                               faults=faults, store=store, journal=journal)
    engine = ServeEngine(cfg, params, registry, peft, slots=args.slots,
                         prompt_buckets=buckets,
                         max_new_tokens=args.gen, faults=faults,
                         journal=journal, mesh=mesh)
    report = None
    if args.restore:
        # warm restart (DESIGN.md §13): rebuild membership + re-admit
        # in-flight requests BEFORE warmup so resume buckets compile there
        report = recover(journal, registry, engine)
        print(f"recovery: {len(report.resume)} in-flight to resume, "
              f"{len(report.completed)} completed / "
              f"{len(report.failed)} failed pre-crash (journaled), "
              f"membership {report.membership}, "
              f"torn_tail={report.torn_tail}, "
              f"orphans_gc={report.orphans_gc}, "
              f"{report.n_records} journal records")
    kb = registry.bank.size_bytes() / 1e3
    tier = (f", merged tier {args.merged_capacity} tenants"
            if args.merged_capacity else "")
    grid = (f", mesh {mesh.shape['data']}x{mesh.shape['model']} "
            f"({engine.n_replicas} slot replicas x "
            f"{engine.slots // engine.n_replicas} slots)"
            if mesh is not None else "")
    print(f"serve engine [{args.method}/{args.backend}]: {args.slots} "
          f"slots, bank capacity {capacity} tenants = {kb:.1f} KB HBM"
          f"{tier}, universe {distinct} tenants, buckets {buckets}, "
          f"max_len {engine.max_len}{grid}")

    t0 = time.perf_counter()
    snap = engine.warmup()
    print(f"warmup (all compiles): {time.perf_counter() - t0:.1f} s  "
          f"traces: {snap}")

    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms > 0 else None
    workload = synthetic_workload(
        n_req, distinct, vocab=cfg.vocab,
        rate_rps=args.rate if args.rate > 0 else None,
        zipf_a=args.zipf_a, prompt_lens=(4, buckets[-1]),
        gen_lens=(2, args.gen), seed=args.seed,
        # half the budget for the first token, the rest for decode
        deadline_ttft_s=deadline_s and deadline_s / 2,
        deadline_total_s=deadline_s)
    # frontend guard: a bad tenant id must raise, never clamp-serve
    # another tenant's adapter
    validate_tenant_ids([r.tenant_id for r in workload], distinct)
    n_distinct = len({r.tenant_id for r in workload})
    print(f"replaying {n_req} requests over {n_distinct} distinct "
          f"tenants (Poisson rate "
          f"{args.rate if args.rate > 0 else 'inf'}/s, "
          f"Zipf a={args.zipf_a}"
          + (f", deadline {args.deadline_ms:.0f} ms" if deadline_s else "")
          + (f", chaos seed {args.chaos_seed}" if faults else "") + ")")

    # the watchdog backstops the per-request deadlines: a wedged slot is
    # cancelled even when its request carries no deadline at all
    sched = Scheduler(engine, watchdog_s=10 * deadline_s
                      if deadline_s else None)
    if report is not None:
        # the dead process journaled these rids: terminals are already
        # accounted, in-flight continue via resume= — neither re-runs
        # from the workload (the workload build is seed-deterministic,
        # so the rids line up across the two processes)
        journaled = report.journaled_rids()
        to_run = [r for r in workload if r.rid not in journaled]
        print(f"restore: {len(to_run)} workload requests not yet "
              f"journaled, {len(report.resume)} resuming")
        done = sched.run(to_run, resume=report.resume)
    else:
        done = sched.run(workload)
    engine.assert_no_retrace(snap)       # degradation never recompiles
    if report is None and n_distinct > capacity \
            and not registry.stats["evictions"]:
        raise AssertionError("distinct tenants exceeded bank capacity "
                             "but nothing was evicted")
    if report is not None:
        # kill-anywhere accounting: every workload rid lands in exactly
        # one bucket across the two process lives
        pools = dict(
            pre_completed=report.completed, pre_failed=report.failed,
            completed=[r for r in done if not r.recovered],
            recovered=[r for r in done if r.recovered],
            failed=sched.failed, shed=sched.dropped)
        seen: dict[int, str] = {}
        for name, pool in pools.items():
            for req in pool:
                if req.rid in seen:
                    raise AssertionError(
                        f"rid {req.rid} accounted twice: "
                        f"{seen[req.rid]} and {name}")
                seen[req.rid] = name
        missing = sorted({r.rid for r in workload} - set(seen))
        if missing:
            raise AssertionError(f"rids in no bucket: {missing}")

    s = summarize(done, scheduler=sched)
    r = registry.stats
    print(f"completed {s['n_requests']} requests "
          f"({s['n_dropped']} shed at admission, "
          f"{len(sched.failed)} failed in flight), "
          f"{s.get('generated_tokens', 0)} tokens in "
          f"{s.get('span_s', 0.0):.2f} s")
    if s["n_requests"]:
        print(f"throughput: {s['throughput_tok_s']:.1f} tok/s   "
              f"per-token latency p50 {s['p50_ms_per_token']:.2f} ms / "
              f"p95 {s['p95_ms_per_token']:.2f} ms   "
              f"ttft p50 {s['ttft_p50_ms']:.1f} ms / "
              f"p95 {s['ttft_p95_ms']:.1f} ms")
    if deadline_s:
        print(f"SLO attainment: ttft "
              f"{s.get('slo_ttft_attained', 1.0) * 100:.1f}%  total "
              f"{s.get('slo_total_attained', 1.0) * 100:.1f}%  "
              f"(shed/cancelled count as missed)")
    acc = sched.accounting()
    if any(acc.values()):
        kinds: dict[str, int] = {}
        for req in (sched.failed + sched.shed_deadline
                    + sched.failed_quarantine):
            kinds[req.error.kind] = kinds.get(req.error.kind, 0) + 1
        print(f"failure accounting: {acc}  outcome kinds: {kinds}")
    if faults is not None:
        print(f"chaos: injected {faults.summary() or '(nothing fired)'}  "
              f"engine {engine.fault_stats}  "
              f"quarantined {sorted(registry.quarantined())}  "
              f"merge-fenced {sorted(registry.merge_fenced())}")
    print(f"registry churn: {r['hits']} hits, {r['misses']} onboards "
          f"({r['evictions']} evictions), "
          f"{r['swap_s'] / max(r['swaps'], 1) * 1e3:.2f} ms/swap")
    if engine.n_replicas > 1:
        print(f"replica placement: {engine.n_replicas} slot groups, "
              f"{sched.stats['replica_affinity_admissions']} "
              f"affinity-routed admissions (adapter rows already in the "
              f"replica's bank region)")
    if registry.merged_capacity:
        t = engine.tier_stats
        total = t["merged_tokens"] + t["bank_tokens"]
        print(f"merged tier: {t['merged_tokens']}/{total} tokens "
              f"({t['merged_tokens'] / max(total, 1) * 100:.1f}% hot-tier "
              f"hit rate), {r['promotions']} promotions / "
              f"{r['demotions']} demotions / "
              f"{r['merged_evictions']} merged evictions "
              f"({r['merges_skipped']} skipped), "
              f"{r['merge_s'] * 1e3:.2f} ms merging, "
              f"{sched.stats['affinity_admissions']} affinity admissions, "
              f"{registry.merged_size_bytes() / 1e3:.1f} KB merged HBM")
    if report is not None:
        print(f"warm restart: {s.get('recovered', 0)} recovered streams, "
              f"restart RTO {s.get('restart_rto_s', 0.0) * 1e3:.1f} ms, "
              f"exactly-one-bucket accounting over {len(seen)} rids OK")
    print(f"jit cache misses after warmup: 0 "
          f"(counters: {engine.jit_cache_misses()})")
    if journal is not None:
        journal.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--method", default="ether",
                    help="PEFT method name "
                         "(repro.core.methods.available())")
    ap.add_argument("--n-blocks", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--merged", action="store_true")
    ap.add_argument("--tenants", type=int, default=0,
                    help="one-shot mode: N>0 compares merged vs "
                         "unmerged-bank decode; --trace mode: device "
                         "bank capacity (default 8)")
    ap.add_argument("--backend", default="auto",
                    choices=("jnp", "pallas", "auto"),
                    help="execution backend for the ETHER hot ops")
    ap.add_argument("--seed", type=int, default=0)
    # continuous-batching replay
    ap.add_argument("--trace", action="store_true",
                    help="replay a synthetic Poisson/Zipf workload "
                         "through the continuous-batching engine")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots (engine batch width)")
    ap.add_argument("--requests", type=int, default=0,
                    help="trace requests (default 3x capacity)")
    ap.add_argument("--distinct-tenants", type=int, default=0,
                    help="tenant universe (default 4x capacity — "
                         "exceeds the bank so eviction is exercised)")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate, requests/s (0 = all "
                         "arrive at t=0)")
    ap.add_argument("--zipf-a", type=float, default=0.8,
                    help="Zipf exponent of the tenant popularity")
    ap.add_argument("--merged-capacity", type=int, default=0,
                    help="hot-tier merged-weight cache entries (0 = "
                         "tierless; hot tenants get their reflection "
                         "absorbed into cached merged weights)")
    ap.add_argument("--prompt-buckets", default="16,32",
                    help="comma-separated prompt pad buckets")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request total SLO deadline in ms (half the "
                         "budget is the TTFT deadline; blown-TTFT "
                         "requests are shed before prefill, blown-total "
                         "cancelled in flight; 0 = no deadlines)")
    ap.add_argument("--journal-dir", default="",
                    help="enable crash-safe serving: durable per-tenant "
                         "adapter store + write-ahead request journal "
                         "rooted here (DESIGN.md §13)")
    ap.add_argument("--restore", action="store_true",
                    help="warm restart: recover membership and resume "
                         "in-flight requests from --journal-dir before "
                         "replaying the not-yet-journaled remainder")
    ap.add_argument("--kill-at-step", type=int, default=None,
                    help="kill-and-restore drill: SIGKILL the process at "
                         "the Nth engine step (exit 137); restart with "
                         "--restore to recover")
    ap.add_argument("--fsync-every", type=int, default=32,
                    help="journal batched-fsync granularity (records per "
                         "fsync; 1 = every record durable)")
    ap.add_argument("--mesh", default="",
                    help="dp,tp device mesh for the sharded serve engine "
                         "(e.g. 2,2): backbone + adapter bank tensor-"
                         "sharded over tp, decode slots replicated into "
                         "dp parallel groups (DESIGN.md §14); pair with "
                         "--fake-devices to run off-TPU")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force N fake CPU host devices before the first "
                         "backend touch (mesh smoke without real "
                         "accelerators)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="seed a FaultPlan over every fault class "
                         "(corrupt/kernel/merge/straggler/evict_storm) "
                         "and replay under injected failures — the "
                         "report adds failure accounting and typed "
                         "outcome counts (DESIGN.md §12)")
    args = ap.parse_args()

    if args.fake_devices:
        # must land before the first backend touch — jax import is fine
        # (backends initialise lazily), jax.devices() is not
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.fake_devices}")

    import jax
    import jax.numpy as jnp
    from repro.common import compile_cache
    from repro.configs import get_config, peft_targets
    from repro.core import execute, methods
    from repro.core.peft import (init_adapter_bank, init_adapters,
                                 merge_params, validate_tenant_ids)
    from repro.core.transforms import PEFTConfig
    from repro.models import EncDecConfig, init_model

    methods.get(args.method)   # typed UnknownMethodError on bad names
    compile_cache.enable()
    cfg = get_config(args.arch, args.variant)
    peft = PEFTConfig(method=args.method, n_blocks=args.n_blocks,
                      targets=peft_targets(args.arch),
                      backend=args.backend)
    rng = jax.random.PRNGKey(args.seed)
    params = init_model(rng, cfg)

    if args.trace:
        run_trace(args, cfg, peft, params, rng)
        return

    B, P = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(
        jax.random.fold_in(rng, 2), (B, P), 0, cfg.vocab)}
    if isinstance(cfg, EncDecConfig):
        batch["frame_embeds"] = jax.random.normal(
            jax.random.fold_in(rng, 3), (B, cfg.n_frames, cfg.d_model),
            cfg.cdt())
    elif getattr(cfg, "frontend", None) == "vision":
        batch["image_embeds"] = jax.random.normal(
            jax.random.fold_in(rng, 3), (B, cfg.n_img_tokens,
                                         cfg.d_frontend), cfg.cdt())

    if args.tenants > 0:
        if args.method not in methods.bank_servable():
            raise SystemExit(f"--tenants requires a bank-servable "
                             f"--method ({', '.join(methods.bank_servable())}"
                             f"); banks gather per-request adapter rows")
        if args.merged:
            raise SystemExit("--merged conflicts with --tenants: the "
                             "tenants mode already runs the merged "
                             "baseline alongside the unmerged bank")
        bank = init_adapter_bank(jax.random.fold_in(rng, 1), params, peft,
                                 args.tenants)
        kb = bank.size_bytes() / 1e3
        print(f"adapter bank [{args.method}]: {args.tenants} tenants = "
              f"{kb:.1f} KB HBM ({kb / args.tenants:.2f} KB/tenant)")
        ids = jax.random.randint(jax.random.fold_in(rng, 4), (B,), 0,
                                 args.tenants, jnp.int32)
        ids = jnp.asarray(validate_tenant_ids(ids, args.tenants))
        print(f"request tenant ids: {ids.tolist()}")

        # --- unmerged bank: one weight set serves all tenants ---
        execute.reset_counters()
        pf, st = make_serving_fns(cfg, peft, args.gen)
        t_pre_u, t_tok_u, gen_u = _timed_generation(
            pf, st, params, bank, batch, args.gen, tenant_ids=ids)
        live = {k: v for k, v in execute.counters().items() if v}
        print(f"[unmerged bank]  prefill: {t_pre_u*1e3:.1f} ms  "
              f"decode: {t_tok_u*1e3:.2f} ms/token  "
              f"(backends traced: {live})")

        # --- merged baseline: tenant 0 absorbed, zero per-step cost,
        #     but the weights can serve only that tenant ---
        merged = merge_params(params, bank.select(0), peft)
        pf_m, st_m = make_serving_fns(cfg, None, args.gen)
        t_pre_m, t_tok_m, _ = _timed_generation(
            pf_m, st_m, merged, None, batch, args.gen)
        print(f"[merged t=0]     prefill: {t_pre_m*1e3:.1f} ms  "
              f"decode: {t_tok_m*1e3:.2f} ms/token")
        print(f"unmerged-bank overhead: "
              f"{(t_tok_u / max(t_tok_m, 1e-9) - 1.0) * 100:+.1f}% "
              f"per decoded token for {args.tenants}-tenant isolation")
        print("generated:", gen_u[0].tolist())
        return

    adapters = init_adapters(jax.random.fold_in(rng, 1), params, peft)
    if args.merged:
        params = merge_params(params, adapters, peft)
        adapters, peft = None, None

    execute.reset_counters()
    pf, st = make_serving_fns(cfg, peft, args.gen)
    t_prefill, t_tok, gen = _timed_generation(pf, st, params, adapters,
                                              batch, args.gen)
    live = {k: v for k, v in execute.counters().items() if v}
    print(f"prefill: {t_prefill*1e3:.1f} ms  "
          f"decode: {t_tok*1e3:.2f} ms/token "
          f"({'merged' if args.merged else 'unmerged adapters'}, "
          f"backend={args.backend})")
    if live:
        print(f"backends traced: {live}")
    print("generated:", gen[0].tolist())


if __name__ == "__main__":
    main()
