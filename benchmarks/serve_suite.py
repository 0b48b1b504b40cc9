"""Tracked serving benchmark suite — the continuous-batching engine's
perf trajectory, measured the same way the kernel/train suites are.

    PYTHONPATH=src python -m benchmarks.run --suite serve \
        --json BENCH_serve.json

writes ``BENCH_serve.json`` at the repo root.  Per backend (jnp and
pallas), five row kinds over the smoke serving model:

``serve_trace`` (what=replay)
    A full Poisson/Zipf replay through Scheduler+ServeEngine with the
    tenant universe exceeding bank capacity (mid-traffic onboarding +
    LRU eviction).  ``us_per_call`` is end-to-end µs per generated
    token (1e6 / throughput); the row also carries ``tok_s``,
    ``p50_ms``/``p95_ms`` per-token decode latency and TTFT tails —
    the headline serving numbers.
``serve_decode_step`` (what=fused_step)
    The jitted fused batched decode step alone, all slots active —
    device-side ms/token floor.
``serve_prefill_slot`` (what=bucket<P>)
    Prefill-into-slot admission at the largest pad bucket.
``tenant_churn`` (what=onboard)
    Registry onboarding cost: the jitted functional bank-row swap
    (`AdapterBank.replace_slot`) for a brand-new tenant.
``serve_merged_step`` (what=merged_baseline)
    Static-batch decode step against tenant-0-merged weights at the
    same batch width — the zero-isolation baseline; payload ``derived``
    records the bank-vs-merged overhead ratio.
``serve_trace_mamba2`` / ``serve_trace_rglru`` / ``serve_trace_hybrid``
    (what=replay) — the same churning replay over the *recurrent*
    decoder families the engine serves since pad-invariant prefill
    (DESIGN.md §10): pure-SSD Mamba-2, a pure RG-LRU pattern, and
    RecurrentGemma's rglru/rglru/local_attn hybrid.  Each row asserts
    zero retraces after warmup and real tenant churn, so the serving
    breadth claim is continuously benchmarked, not just unit-tested.
``serve_trace_delora`` / ``serve_trace_hyperadapt`` (what=replay)
    The same churning replay with the bank serving the *other*
    registry methods (DESIGN.md §15) — rank-r DeLoRA deltas and
    HyperAdapt row/column rescales through the method-parametric
    AdapterBank, onboarding swaps and all, still zero retraces.
``serve_trace_tiered`` / ``serve_trace_bank`` (what=zipf<a> | hotshift)
    The tiered grid (DESIGN.md §11): full replays with the merged hot
    tier enabled vs a pure-bank control at identical grid + workload,
    swept over Zipf skew (uniform → heavy head) plus a mid-trace
    hot-set shift row; rows carry tier stats (merged-token fraction,
    promotions/demotions, merge ms, affinity admissions) and payload
    ``derived`` records the tiered-vs-bank throughput ratios — each
    measured as the median over interleaved tiered/bank replay pairs
    (``_tiered_pair``), the drift-immune estimator the acceptance
    asserts on.
``serve_hot_step`` (what=merged_tier_step)
    The engine's third jitted entry point — the merged-weights decode
    step — timed saturated; ``derived`` records its ratio to the
    static merged baseline (acceptance: ≤ 1.05 on jnp serving rows).
``serve_guard_overhead`` (what=nonfinite_guard)
    The fused step with its in-jit non-finite-logits guard (finiteness
    of the sampled logit, an O(slots) gather — DESIGN.md §12) vs an
    ungated control (same body, flag output dropped → XLA DCEs the
    guard); ``derived`` records the paired ratio (acceptance: ≤ 1.05
    on jnp serving rows — the guard is free on the healthy path).
``serve_trace_degraded`` (what=corrupt|kernel|merge|straggler|
    evict_storm) — the degraded-mode grid (DESIGN.md §12): one full
    replay per injected fault class, each completing with typed
    per-request outcomes, full accounting, zero retraces, and bounded
    wall-clock overhead vs a healthy twin (``derived``).
``serve_journal_overhead`` (what=wal)
    The full replay with the write-ahead journal + durable store
    attached (DESIGN.md §13) vs an unjournaled twin at identical grid +
    workload — interleaved pairs like the guard gate; payload
    ``derived['journal_vs_plain_<backend>']`` records the low-quantile
    pair ratio (acceptance: ≤ 1.05 on the jnp serving grid — crash
    safety is near-free on the healthy path).
``serve_recovery`` (what=warm_restart)
    Kill-and-restore drill as a tracked number: a scheduled
    SimulatedCrash kills a journaled replay mid-trace, a fresh engine
    recovers (membership rebuilt, in-flight resumed as extended
    prefills) and finishes it with exactly-one-bucket accounting and
    zero retraces; ``us_per_call`` is the measured restart RTO (engine
    start → first resumed token).
``serve_trace_sharded`` (what=mesh<dp>x<tp>)
    The scaling-efficiency grid (DESIGN.md §14): full churning replays
    on dp×tp device meshes (tensor-sharded backbone + adapter bank over
    ``model``, replica-parallel slot groups over ``data``), run in an
    8-fake-device subprocess on the jnp backend; each row proves zero
    retraces, churn, and oracle-equivalence, and payload ``derived``
    carries per-mesh tok/s normalized to the 1x1 row
    (``sharded_scaling_<dp>x<tp>``).  pallas rows replay a 1-device
    mesh in-process (interpret-mode kernels under multi-device GSPMD
    are unsupported).
``serve_sharded_overhead`` (what=mesh1x1_vs_plain)
    The fused step on a trivial 1x1-mesh engine vs the plain engine —
    interleaved pairs like the guard gate; ``derived`` records the
    low-quantile pair ratio (acceptance: ≤ 1.05 on jnp serving rows —
    sharding machinery must be free until the mesh has >1 device).

Honest labeling off-TPU mirrors kernels_suite: the pallas backend runs
the interpret-mode emulator there, so pallas rows are timed at the tiny
grid once with ``mode: interpret`` (compiled on a real TPU); jnp rows
are the CPU-comparable numbers.  The suite FAILS (SystemExit) if any
(row kind, backend) pair is missing — CI runs ``--shapes tiny`` as a
smoke gated against ``benchmarks/baselines/BENCH_serve_tiny.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._common import time_us

ROW_OPS = ("serve_trace", "serve_decode_step", "serve_prefill_slot",
           "tenant_churn", "serve_merged_step", "serve_trace_mamba2",
           "serve_trace_rglru", "serve_trace_hybrid",
           "serve_trace_tiered", "serve_trace_bank", "serve_hot_step",
           "serve_guard_overhead", "serve_trace_degraded",
           "serve_journal_overhead", "serve_recovery",
           "serve_trace_sharded", "serve_sharded_overhead",
           "serve_trace_delora", "serve_trace_hyperadapt")

SERVE_SHAPES = {
    "serving": dict(slots=8, buckets=(16, 32), gen=16, capacity=16,
                    universe=64, requests=48, rate=None, seed=0),
    "tiny": dict(slots=2, buckets=(8,), gen=4, capacity=3, universe=8,
                 requests=6, rate=None, seed=0),
    # recurrent-family replays run one small grid at every shape level:
    # the row exists to keep the serving-breadth claim benchmarked (and
    # retrace-free), not to stress a big batch
    "family": dict(slots=2, buckets=(8,), gen=4, capacity=2, universe=6,
                   requests=8, rate=None, seed=0),
    # tiered grid: hot-tenant merged tier vs pure-bank control, swept
    # over Zipf skew (zipf_a=0.0 is the uniform no-regression control).
    # Fixed gen_lens synchronize slot turnover so whole batches admit
    # and retire together — that is what lets affinity admission build
    # the single-tenant batches the merged tier needs (variable gens
    # leave cold stragglers poisoning every batch; the hotshift row and
    # the plain serve_trace rows keep variable lengths covered).  The
    # wide affinity_lookahead gives peek_hot enough queue to seed pure
    # hot-tenant runs.  hotshift re-draws the hot set mid-trace so one
    # replay exercises promotion AND demotion/eviction.
    # method=etherplus: ETHER+ carries the largest per-token reflect
    # tax of the bank methods (two hyperplane pairs per target), so it
    # is both the variant the merged tier helps most and the one the
    # paper prefers for quality — the bank control pays the same tax,
    # the comparison stays method-matched
    "tiered": dict(slots=4, buckets=(16,), gen=32, capacity=12,
                   universe=48, requests=64, rate=None, seed=0,
                   method="etherplus", gen_lens=(32, 32),
                   affinity_lookahead=96,
                   merged_capacity=6, promote_after=3, window=32,
                   min_dwell=16, hot_permutation=3,
                   zipf=(0.0, 1.1, 1.5), shift_hot_at=32),
    "tiered_tiny": dict(slots=2, buckets=(8,), gen=4, capacity=3,
                        universe=8, requests=10, rate=None, seed=0,
                        method="etherplus", gen_lens=(4, 4),
                        affinity_lookahead=16,
                        merged_capacity=2, promote_after=2, window=8,
                        min_dwell=0, hot_permutation=3,
                        zipf=(0.0, 1.5), shift_hot_at=5),
    # sharded grid (DESIGN.md §14): full replays on a dp×tp device mesh
    # of fake CPU devices (8-device subprocess — jax locks the device
    # count at backend init, so the mesh rows cannot run in the bench
    # process).  Fake devices share the same physical cores, so the
    # scaling-efficiency columns track the sharding machinery's
    # overhead trend, not real speedup; slots must divide by dp.
    "sharded": dict(slots=4, buckets=(8, 16), gen=8, capacity=8,
                    universe=16, requests=16, rate=None, seed=0,
                    meshes=((1, 1), (1, 2), (2, 2), (2, 4))),
    "sharded_tiny": dict(slots=2, buckets=(8,), gen=4, capacity=2,
                         universe=6, requests=6, rate=None, seed=0,
                         meshes=((1, 1), (1, 2), (2, 2))),
}

_POLICY_KEYS = ("merged_capacity", "promote_after", "window", "min_dwell")


def _family_archs():
    """(op suffix → config, peft targets) for the recurrent families."""
    from repro.configs import get_config, peft_targets
    from repro.models import ModelConfig
    rglru_cfg = ModelConfig(
        name="rglru-smoke", n_layers=2, d_model=64, n_heads=4, n_kv=1,
        d_ff=128, vocab=256, block_pattern=("rglru",), rnn_width=64,
        rnn_heads=4, act="gelu_tanh", remat="none")
    return (
        ("serve_trace_mamba2", get_config("mamba2-1.3b", "smoke"),
         peft_targets("mamba2-1.3b")),
        ("serve_trace_rglru", rglru_cfg, "in_x|in_y|out_proj"),
        ("serve_trace_hybrid", get_config("recurrentgemma-9b", "smoke"),
         peft_targets("recurrentgemma-9b")),
    )


def _build(backend: str, grid: dict, cfg=None, targets=None, faults=None,
           store=None, journal=None, mesh=None):
    from repro.configs import get_config, peft_targets
    from repro.core.transforms import PEFTConfig
    from repro.models import init_model
    from repro.serving import AdapterRegistry, ServeEngine

    if cfg is None:
        cfg = get_config("smollm-360m", "smoke")
        targets = peft_targets("smollm-360m")
    peft = PEFTConfig(method=grid.get("method", "ether"), n_blocks=4,
                      targets=targets, backend=backend)
    rng = jax.random.PRNGKey(0)
    params = init_model(rng, cfg)
    policy = {k: grid[k] for k in _POLICY_KEYS if k in grid}
    registry = AdapterRegistry(params, peft, grid["capacity"],
                               n_tenants=grid["universe"],
                               rng=jax.random.fold_in(rng, 1),
                               faults=faults, store=store,
                               journal=journal, **policy)
    engine = ServeEngine(cfg, params, registry, peft,
                         slots=grid["slots"],
                         prompt_buckets=grid["buckets"],
                         max_new_tokens=grid["gen"], faults=faults,
                         journal=journal, mesh=mesh)
    return cfg, peft, params, registry, engine


_TIER_STATS = ("promotions", "demotions", "merged_evictions",
               "merges_skipped")


def _paired_us(fn_a, fn_b, iters: int, pairs: int = 5, q: float = 0.5):
    """Interleaved A/B step timing → (min_us_a, min_us_b, ``q``-th
    quantile of the a/b pair ratios).  Same drift rationale as
    ``_tiered_pair``, for the single-step rows: two back-to-back
    ``time_us`` calls can disagree by more than the few-percent ratios
    the acceptance gates, so the gated ratio must come from adjacent
    pairs, not separate mins.  ``q`` defaults to the median; a
    one-sided upper-bound gate on a ratio whose true value is ~1.0
    (the guard gate) should pass a LOW quantile instead — scheduler
    noise only ever inflates individual pairs (contention is one-
    sided), while a real systematic regression shifts every pair, so
    a low quantile rejects the former and still trips on the latter."""
    us_a = us_b = float("inf")
    ratios = []
    for _ in range(pairs):
        a = time_us(fn_a, iters=iters, reps=1)
        b = time_us(fn_b, iters=iters, reps=1)
        us_a, us_b = min(us_a, a), min(us_b, b)
        ratios.append(a / max(b, 1e-9))
    return us_a, us_b, sorted(ratios)[int(q * (len(ratios) - 1))]


def _workload(grid: dict, cfg, wl_kwargs: dict | None = None):
    """Build + validate the synthetic trace for a replay grid.
    ``wl_kwargs`` forwards tiered-grid axes (zipf_a, hot_permutation,
    shift_hot_at)."""
    from repro.core.peft import validate_tenant_ids
    from repro.serving import synthetic_workload

    wl = synthetic_workload(
        grid["requests"], grid["universe"], vocab=cfg.vocab,
        rate_rps=grid["rate"], prompt_lens=(4, grid["buckets"][-1]),
        gen_lens=grid.get("gen_lens", (2, grid["gen"])),
        seed=grid["seed"], **(wl_kwargs or {}))
    validate_tenant_ids([r.tenant_id for r in wl], grid["universe"])
    return wl


def _one_replay(op: str, grid: dict, registry, engine, workload) -> dict:
    """One timed Scheduler replay → summarize() dict + tier-stat deltas.

    The collector is paused for the timed region: on a small (even
    1-core) box, GC pauses are the single biggest wall-clock jitter
    source for sub-second replays, and they land in whichever replay
    happens to cross the allocation threshold."""
    import copy
    import gc

    from repro.serving import Scheduler, summarize

    ev0 = registry.stats["evictions"]
    t0 = dict(engine.tier_stats)
    r0 = {k: registry.stats[k] for k in _TIER_STATS}
    merge_s0 = registry.stats["merge_s"]
    sched = Scheduler(
        engine, affinity_lookahead=grid.get("affinity_lookahead"))
    reqs = copy.deepcopy(workload)
    gc.collect()
    gc.disable()
    try:
        done = sched.run(reqs, clock=lambda: float("inf"))
    finally:
        gc.enable()
    if sched.dropped or not done:
        # the synthetic workload is entirely valid for this engine: a
        # drop here means admission regressed into rejecting good
        # requests — which must fail the suite, not pass the gate with
        # quietly shed load
        raise SystemExit(
            f"{op}: {len(sched.dropped)} of {len(workload)} valid "
            f"requests rejected at admission")
    cand = summarize(done, dropped=len(sched.dropped))
    # every reported field must describe the SAME rep: later reps start
    # with a warm registry/merged tier, so churn differs
    cand["evictions"] = registry.stats["evictions"] - ev0
    tok = {k: engine.tier_stats[k] - t0[k] for k in t0}
    total = tok["merged_tokens"] + tok["bank_tokens"]
    cand["tier"] = dict(
        merged_token_frac=round(tok["merged_tokens"] / max(total, 1), 3),
        merged_steps=tok["merged_steps"], bank_steps=tok["bank_steps"],
        merge_ms=round((registry.stats["merge_s"] - merge_s0) * 1e3, 3),
        affinity_admissions=sched.stats["affinity_admissions"],
        **{k: registry.stats[k] - r0[k] for k in _TIER_STATS})
    return cand


def _check_churn(op: str, grid: dict, registry, workload) -> None:
    if (len({r.tenant_id for r in workload}) > grid["capacity"]
            and not registry.stats["evictions"]):
        raise SystemExit(f"{op}: universe exceeded capacity but nothing "
                         f"was evicted — churn not exercised")


def _row(op: str, backend: str, mode: str, grid: dict, cfg, s: dict,
         what: str) -> dict:
    return dict(
        op=op, backend=backend, kind="decode", what=what, mode=mode,
        shape=dict(batch=grid["slots"], tokens=1, d=cfg.d_model),
        us_per_call=round(1e6 / max(s["throughput_tok_s"], 1e-9), 2),
        tok_s=round(s["throughput_tok_s"], 2),
        p50_ms=round(s["p50_ms_per_token"], 3),
        p95_ms=round(s["p95_ms_per_token"], 3),
        ttft_p50_ms=round(s["ttft_p50_ms"], 2),
        ttft_p95_ms=round(s["ttft_p95_ms"], 2),
        n_requests=s["n_requests"], n_dropped=s["n_dropped"],
        evictions=s["evictions"], tier=s["tier"])


def _replay_entry(op: str, backend: str, mode: str, grid: dict,
                  cfg, registry, engine, reps: int = 2,
                  what: str = "replay", wl_kwargs: dict | None = None
                  ) -> dict:
    """One churning Scheduler replay → a serve_trace-style row.  Asserts
    zero retraces after warmup and (universe > capacity ⇒) evictions.

    The replay is end-to-end wall clock (host scheduling included), so
    like ``time_us`` the row keeps the best of ``reps`` replays — the
    min is the stable systematic-cost estimator on a contended box.
    The row carries the best rep's tier stats (merged-token fraction,
    promotions/demotions, merge ms, affinity admissions) alongside the
    latency tails."""
    snap = engine.warmup()
    workload = _workload(grid, cfg, wl_kwargs)
    s = None
    for _ in range(max(1, reps)):
        cand = _one_replay(op, grid, registry, engine, workload)
        if s is None or cand["throughput_tok_s"] > s["throughput_tok_s"]:
            s = cand
    engine.assert_no_retrace(snap)
    _check_churn(op, grid, registry, workload)
    return _row(op, backend, mode, grid, cfg, s, what)


def _tiered_pair(backend: str, mode: str, tgrid: dict, cfg,
                 reps: int = 6, what: str = "replay",
                 wl_kwargs: dict | None = None):
    """Tiered engine vs pure-bank control as ONE interleaved A/B run.

    The two replays the acceptance ratio compares are each well under a
    second of wall clock, on a box whose throughput can drift ±20% on
    that same timescale — timing all reps of one side and then all reps
    of the other lets the drift land on a single side of the ratio.
    Interleaving pairs each tiered replay with an immediately-adjacent
    bank replay, and the reported ratio is the MEDIAN of per-pair
    ratios: drift cancels within a pair, and the median rejects the
    odd pair that straddles a load burst.  Row ``tok_s`` stays
    best-of-reps per side, same estimator as every other replay row.

    Returns ``(rows, ratio, hot_registry, hot_engine)`` — the tiered
    row first, then the bank control."""
    grids = (dict(tgrid), dict(tgrid, merged_capacity=0))
    ops = ("serve_trace_tiered", "serve_trace_bank")
    built = [_build(backend, g)[3:] for g in grids]   # (registry, engine)
    snaps = [eng.warmup() for _, eng in built]
    # identical trace on both sides (grids differ only in the policy)
    workload = _workload(grids[0], cfg, wl_kwargs)
    best = [None, None]
    ratios = []
    for _ in range(max(1, reps)):
        pair = []
        for i, (reg, eng) in enumerate(built):
            cand = _one_replay(ops[i], grids[i], reg, eng, workload)
            if (best[i] is None or cand["throughput_tok_s"]
                    > best[i]["throughput_tok_s"]):
                best[i] = cand
            pair.append(cand["throughput_tok_s"])
        ratios.append(pair[0] / max(pair[1], 1e-9))
    for i, (reg, eng) in enumerate(built):
        eng.assert_no_retrace(snaps[i])
        _check_churn(ops[i], grids[i], reg, workload)
    ratio = round(sorted(ratios)[len(ratios) // 2], 3)
    rows = [_row(ops[i], backend, mode, grids[i], cfg, best[i], what)
            for i in range(2)]
    return rows, ratio, built[0][0], built[0][1]


def _saturated_state(engine, grid):
    """Engine state with every slot mid-decode (step-timing harness)."""
    rng = np.random.default_rng(7)
    state = engine._state
    b = grid["buckets"][-1]
    for slot in range(engine.slots):
        tokens = np.zeros((1, b), np.int32)
        plen = b // 2
        tokens[0, :plen] = rng.integers(0, engine.cfg.vocab, plen)
        state, _, _ = engine._prefill_fns[b](
            engine.params, engine.registry.bank, state, tokens,
            int(plen), int(slot), int(slot % engine.registry.capacity),
            int(grid["gen"]))
    return state


def _chaos_replay(op: str, grid: dict, registry, engine, workload, *,
                  clock=None):
    """Failure-tolerant replay runner for the degraded-mode grid.

    Unlike ``_one_replay`` (which SystemExits on ANY shed load, because
    its workload must admit cleanly), a fault-injected replay is
    EXPECTED to shed/fail requests — what it must prove instead is full
    accounting: every request either completed or carries a typed
    :class:`~repro.serving.scheduler.RequestError`, and none vanished.
    Returns ``(done, scheduler, wall_s)``."""
    import copy
    import gc
    import time

    from repro.serving import Scheduler

    sched = Scheduler(engine,
                      affinity_lookahead=grid.get("affinity_lookahead"))
    reqs = copy.deepcopy(workload)
    gc.collect()
    gc.disable()
    t0 = time.perf_counter()
    try:
        done = sched.run(reqs, clock=clock)
    finally:
        gc.enable()
    wall = time.perf_counter() - t0
    n = len(done) + len(sched.failed) + len(sched.dropped)
    if n != len(workload):
        raise SystemExit(f"{op}: only {n} of {len(workload)} requests "
                         f"accounted for after the degraded replay")
    untyped = [r.rid for r in (sched.failed + sched.shed_deadline
                               + sched.failed_quarantine)
               if r.error is None]
    if untyped:
        raise SystemExit(f"{op}: failed requests without typed outcomes: "
                         f"{untyped}")
    return done, sched, wall


def _degraded_entries(backend: str, mode: str, grid: dict, cfg,
                      derived: dict) -> list[dict]:
    """Degraded-mode grid: one full replay per fault class (DESIGN.md
    §12), each against a fresh engine with a deterministic FaultPlan.
    Every row proves (a) the replay completed with full typed
    accounting, (b) the fault actually fired, (c) zero retraces, and
    records its wall-clock overhead vs a healthy twin replay
    (``derived['degraded_overhead_<class>_<backend>']``)."""
    from collections import Counter

    from repro.serving import summarize
    from repro.serving.faults import FaultPlan

    inf_clock = lambda: float("inf")
    rows = []
    # healthy twin: same grid, no plan — the overhead denominator
    _, _, _, hreg, heng = _build(backend, grid)
    snap = heng.warmup()
    workload = _workload(grid, cfg)
    _, _, wall_h = _chaos_replay("serve_trace_degraded:healthy", grid,
                                 hreg, heng, workload, clock=inf_clock)
    heng.assert_no_retrace(snap)
    common = [t for t, _ in Counter(r.tenant_id
                                    for r in workload).most_common(2)]
    plans = {
        "corrupt": FaultPlan(corrupt_adapters={common[0]: "nan",
                                               common[-1]: "inf"}),
        "kernel": FaultPlan(kernel_raise_at=frozenset({2}),
                            kernel_persistent=True),
        "merge": FaultPlan(merge_fail={common[0]: 10 ** 9}),
        "straggler": FaultPlan(slow_steps={1: 0.01, 3: 0.01}),
        "evict_storm": FaultPlan(evict_storm_at=frozenset({2, 4})),
    }
    for cls, plan in plans.items():
        g = dict(grid)
        if cls == "merge":
            # merge faults need a hot tier to fail promotions in
            g.update(merged_capacity=2, promote_after=2, window=16,
                     min_dwell=0)
        op = f"serve_trace_degraded:{cls}"
        _, _, _, reg, eng = _build(backend, g, faults=plan)
        snap = eng.warmup()
        wl = _workload(g, cfg)
        # stragglers inject real host delays, so they replay on the real
        # clock; the other classes replay saturated like every bench row
        clock = None if cls == "straggler" else inf_clock
        done, sched, wall = _chaos_replay(op, g, reg, eng, wl,
                                          clock=clock)
        eng.assert_no_retrace(snap)
        fired = plan.summary()
        if not fired.get(cls):
            raise SystemExit(f"{op}: fault class never fired ({fired})")
        if cls == "corrupt" and not reg.stats["quarantine_evictions"]:
            raise SystemExit(f"{op}: corrupt adapters served but no "
                             f"tenant was quarantine-evicted")
        if cls == "merge" and not reg.stats["merge_failures"]:
            raise SystemExit(f"{op}: merge faults fired but no tenant "
                             f"was fenced")
        derived[f"degraded_overhead_{cls}_{backend}"] = round(
            wall / max(wall_h, 1e-9), 3)
        s = summarize(done, scheduler=sched)
        errs = sorted({r.error.kind for r in
                       (sched.failed + sched.shed_deadline
                        + sched.failed_quarantine)})
        rows.append(dict(
            op="serve_trace_degraded", backend=backend, kind="decode",
            what=cls, mode=mode,
            shape=dict(batch=grid["slots"], tokens=1, d=cfg.d_model),
            us_per_call=round(
                1e6 / max(s.get("throughput_tok_s", 0.0), 1e-9), 2),
            n_requests=s["n_requests"],
            accounting=sched.accounting(),
            fault_fired=fired, error_kinds=errs))
    return rows


def _crash_safety_entries(backend: str, mode: str, grid: dict, cfg,
                          derived: dict) -> list[dict]:
    """Crash-safe serving rows (DESIGN.md §13).

    ``serve_journal_overhead``: the full churning replay with the
    write-ahead journal + durable store attached vs an unjournaled twin
    — interleaved pairs, low-quantile ratio (same one-sided-gate
    rationale as the guard pair in ``_paired_us``).

    ``serve_recovery``: a scheduled crash (SimulatedCrash at a mid-trace
    engine step, ``fsync_every=1`` so the journal is complete at death)
    kills a journaled replay; a FRESH registry/engine recovers over the
    same disk and finishes the trace.  The row is gated on the drill
    actually working: crash fired, in-flight requests resumed, every
    workload rid in exactly one accounting bucket, zero retraces.
    ``us_per_call`` is the measured restart RTO."""
    import copy
    import os
    import shutil
    import tempfile

    from repro.serving import (AdapterStore, Journal, Scheduler,
                               SimulatedCrash, recover, summarize)
    from repro.serving.faults import FaultPlan

    inf_clock = lambda: float("inf")                    # noqa: E731
    rows = []

    # --- WAL overhead: journaled vs plain twin ------------------------
    jroot = tempfile.mkdtemp(prefix="bench_wal_")
    try:
        store = AdapterStore(os.path.join(jroot, "adapters"))
        journal = Journal(os.path.join(jroot, "journal.jsonl"),
                          fsync_every=32)
        _, _, _, jreg, jeng = _build(backend, grid, store=store,
                                     journal=journal)
        _, _, _, preg, peng = _build(backend, grid)
        snap_j, snap_p = jeng.warmup(), peng.warmup()
        workload = _workload(grid, cfg)
        best = None
        ratios = []
        for _ in range(8 if backend == "jnp" else 2):
            cj = _one_replay("serve_journal_overhead", grid, jreg, jeng,
                             workload)
            cp = _one_replay("serve_journal_overhead:plain", grid, preg,
                             peng, workload)
            if (best is None or cj["throughput_tok_s"]
                    > best["throughput_tok_s"]):
                best = cj
            ratios.append(cp["throughput_tok_s"]
                          / max(cj["throughput_tok_s"], 1e-9))
        jeng.assert_no_retrace(snap_j)
        peng.assert_no_retrace(snap_p)
        journal.close()
        derived[f"journal_vs_plain_{backend}"] = round(
            sorted(ratios)[int(0.25 * (len(ratios) - 1))], 3)
        rows.append(_row("serve_journal_overhead", backend, mode, grid,
                         cfg, best, "wal"))
    finally:
        shutil.rmtree(jroot, ignore_errors=True)

    # --- warm-restart RTO: crash mid-trace, recover, resume -----------
    rroot = tempfile.mkdtemp(prefix="bench_recovery_")
    try:
        plan = FaultPlan(crash_at={"step": max(4, grid["requests"] // 2)})
        store1 = AdapterStore(os.path.join(rroot, "adapters"),
                              faults=plan)
        journal1 = Journal(os.path.join(rroot, "journal.jsonl"),
                           fsync_every=1, faults=plan)
        _, _, _, reg1, eng1 = _build(backend, grid, faults=plan,
                                     store=store1, journal=journal1)
        eng1.warmup()
        workload = _workload(grid, cfg)
        try:
            Scheduler(eng1).run(copy.deepcopy(workload), clock=inf_clock)
        except SimulatedCrash:
            pass
        if "crash:step" not in plan.fired:
            raise SystemExit("serve_recovery: the scheduled crash never "
                             "fired — the drill measured nothing")
        store2 = AdapterStore(os.path.join(rroot, "adapters"))
        journal2 = Journal(os.path.join(rroot, "journal.jsonl"),
                           fsync_every=1)
        _, _, _, reg2, eng2 = _build(backend, grid, store=store2,
                                     journal=journal2)
        report = recover(journal2, reg2, eng2)
        if not report.resume:
            raise SystemExit("serve_recovery: nothing was in flight at "
                             "the crash — no RTO to measure")
        snap = eng2.warmup()
        sched = Scheduler(eng2)
        rest = [r for r in workload
                if r.rid not in report.journaled_rids()]
        done = sched.run(copy.deepcopy(rest), clock=inf_clock,
                         resume=report.resume)
        eng2.assert_no_retrace(snap)
        journal2.close()
        seen: dict[int, str] = {}
        pools = dict(pre_completed=report.completed,
                     pre_failed=report.failed, finished=done,
                     failed=sched.failed, shed=sched.dropped)
        for name, pool in pools.items():
            for r in pool:
                if r.rid in seen:
                    raise SystemExit(f"serve_recovery: rid {r.rid} "
                                     f"accounted twice ({seen[r.rid]} "
                                     f"and {name})")
                seen[r.rid] = name
        if set(seen) != {r.rid for r in workload}:
            raise SystemExit("serve_recovery: accounting does not cover "
                             "the workload exactly once")
        s = summarize(done, scheduler=sched)
        rto = s.get("restart_rto_s")
        if rto is None:
            raise SystemExit("serve_recovery: requests resumed but no "
                             "restart RTO was measured")
        rows.append(dict(
            op="serve_recovery", backend=backend, kind="decode",
            what="warm_restart", mode=mode,
            shape=dict(batch=grid["slots"], tokens=1, d=cfg.d_model),
            us_per_call=round(rto * 1e6, 2),
            n_resumed=len(report.resume),
            recovered=s.get("recovered", 0),
            pre_completed=len(report.completed),
            journal_records=report.n_records))
    finally:
        shutil.rmtree(rroot, ignore_errors=True)
    return rows


# child template for the sharded grid: jax locks the host device count
# at first backend init, so the mesh replays run in an 8-fake-device
# subprocess (repro.common.subproc).  The child only sees PYTHONPATH=src
# — repro imports only, no ``benchmarks``.
_SHARDED_CHILD = r'''
import copy, json
import jax
from repro.configs import get_config, peft_targets
from repro.core.transforms import PEFTConfig
from repro.launch.mesh import make_host_mesh
from repro.models import init_model
from repro.serving import (AdapterRegistry, Scheduler, ServeEngine,
                           oracle_tokens, summarize, synthetic_workload)

GRID = __GRID__
cfg = get_config("smollm-360m", "smoke")
rng = jax.random.PRNGKey(0)
params = init_model(rng, cfg)
rows = []
for dp, tp in GRID["meshes"]:
    peft = PEFTConfig(method="ether", n_blocks=4,
                      targets=peft_targets("smollm-360m"), backend="jnp")
    registry = AdapterRegistry(params, peft, GRID["capacity"],
                               n_tenants=GRID["universe"],
                               rng=jax.random.fold_in(rng, 1))
    engine = ServeEngine(cfg, params, registry, peft,
                         slots=GRID["slots"],
                         prompt_buckets=tuple(GRID["buckets"]),
                         max_new_tokens=GRID["gen"],
                         mesh=make_host_mesh(dp, tp))
    snap = engine.warmup()
    wl = synthetic_workload(GRID["requests"], GRID["universe"],
                            vocab=cfg.vocab, rate_rps=None,
                            prompt_lens=(4, GRID["buckets"][-1]),
                            gen_lens=(2, GRID["gen"]), seed=GRID["seed"])
    best, aff = None, 0
    for _ in range(2):
        sched = Scheduler(engine)
        done = sched.run(copy.deepcopy(wl), clock=lambda: float("inf"))
        assert len(done) == len(wl) and not sched.dropped, \
            (dp, tp, len(done), len(sched.dropped))
        s = summarize(done)
        if best is None or s["throughput_tok_s"] > best["throughput_tok_s"]:
            best = s
            aff = sched.stats["replica_affinity_admissions"]
    engine.assert_no_retrace(snap)
    assert registry.stats["evictions"] > 0, (dp, tp, "no churn")
    # the scaling row stays honest: the sharded engine must still be
    # token-identical to the single-tenant tier-faithful oracle
    for req in done[:2]:
        assert req.tokens == oracle_tokens(cfg, peft, params, registry,
                                           req), (dp, tp, req.rid)
    rows.append(dict(
        mesh=[dp, tp], replicas=engine.n_replicas,
        tok_s=round(best["throughput_tok_s"], 2),
        p50_ms=round(best["p50_ms_per_token"], 3),
        p95_ms=round(best["p95_ms_per_token"], 3),
        ttft_p50_ms=round(best["ttft_p50_ms"], 2),
        ttft_p95_ms=round(best["ttft_p95_ms"], 2),
        n_requests=best["n_requests"],
        evictions=registry.stats["evictions"], affinity=aff))
print("SHARDED_JSON=" + json.dumps(rows))
'''


def _sharded_entries(backend: str, mode: str, grid_name: str, cfg,
                     derived: dict) -> list[dict]:
    """Mesh-sharded replay grid (DESIGN.md §14).

    jnp rows replay the full trace on every dp×tp mesh of the grid in
    one 8-fake-device subprocess (the bench process has already locked
    jax to the host's real device count): each mesh row proves zero
    retraces, real churn, and oracle-equivalence, and carries the usual
    throughput/latency fields plus the replica count.  The derived
    ``sharded_scaling_<dp>x<tp>`` columns normalize tok/s to the mesh
    1x1 row — on fake CPU devices (shared cores) they track the
    sharding machinery's overhead trend, not real speedup, which is
    exactly the regression signal --compare needs.

    pallas rows run ONE in-process mesh-1x1 replay at the tiny sharded
    grid: interpret-mode kernels under multi-device GSPMD are not a
    supported configuration, and a 1-device mesh already exercises the
    sharded code path (NamedSharding params/banks, constrained states).
    """
    import json

    sname = "sharded" if grid_name == "serving" else "sharded_tiny"
    grid = dict(SERVE_SHAPES[sname])
    if backend != "jnp":
        from repro.launch.mesh import make_host_mesh
        sgrid = dict(SERVE_SHAPES["sharded_tiny"])
        sgrid.pop("meshes")
        _, _, _, sreg, seng = _build(backend, sgrid,
                                     mesh=make_host_mesh(1, 1))
        return [_replay_entry("serve_trace_sharded", backend, mode,
                              sgrid, cfg, sreg, seng, what="mesh1x1")]

    from repro.common.subproc import run_subprocess
    child = _SHARDED_CHILD.replace("__GRID__", repr(grid))
    out = run_subprocess(child, devices=8, timeout=580)
    payload = next(l for l in out.splitlines()
                   if l.startswith("SHARDED_JSON="))
    mesh_rows = json.loads(payload[len("SHARDED_JSON="):])
    base = next(r["tok_s"] for r in mesh_rows if r["mesh"] == [1, 1])
    entries = []
    for r in mesh_rows:
        dp, tp = r["mesh"]
        entries.append(dict(
            op="serve_trace_sharded", backend=backend, kind="decode",
            what=f"mesh{dp}x{tp}", mode=mode,
            shape=dict(batch=grid["slots"], tokens=1, d=cfg.d_model,
                       dp=dp, tp=tp),
            us_per_call=round(1e6 / max(r["tok_s"], 1e-9), 2),
            tok_s=r["tok_s"], p50_ms=r["p50_ms"], p95_ms=r["p95_ms"],
            ttft_p50_ms=r["ttft_p50_ms"],
            ttft_p95_ms=r["ttft_p95_ms"],
            n_requests=r["n_requests"], evictions=r["evictions"],
            replicas=r["replicas"],
            replica_affinity_admissions=r["affinity"]))
        derived[f"sharded_scaling_{dp}x{tp}_{backend}"] = round(
            r["tok_s"] / max(base, 1e-9), 3)
    return entries


def run_suite(shapes: str = "serving", include_interp: bool = False,
              iters: int | None = None) -> dict:
    """Time the serving rows per backend; returns the JSON payload.

    Raises SystemExit if any (op, backend) row is missing (CI contract).
    """
    from repro.core.peft import merge_params
    from repro.launch.serve import make_serving_fns

    grid_name = "serving" if shapes == "serving" else "tiny"
    on_tpu = jax.default_backend() == "tpu"
    entries = []
    derived = {}
    for backend in ("jnp", "pallas"):
        emulated = backend == "pallas" and not on_tpu
        grid = dict(SERVE_SHAPES["tiny" if (emulated and not include_interp)
                                 else grid_name])
        mode = ("interpret" if emulated else
                "compiled" if backend == "pallas" else "xla")
        cfg, peft, params, registry, engine = _build(backend, grid)
        d = cfg.d_model

        # --- full replay (throughput + latency tails + churn) --------
        entries.append(_replay_entry("serve_trace", backend, mode, grid,
                                     cfg, registry, engine))

        # --- recurrent families: pad-invariant slot serving -----------
        fgrid = dict(SERVE_SHAPES["family"])
        for fop, fcfg, ftargets in _family_archs():
            _, _, _, freg, feng = _build(backend, fgrid, cfg=fcfg,
                                         targets=ftargets)
            entries.append(_replay_entry(fop, backend, mode, fgrid,
                                         fcfg, freg, feng))

        # --- cross-method replays: the non-ETHER registry methods -----
        # (DESIGN.md §15): same churning trace at the family grid,
        # method-parametric bank + merges — the row existing at all
        # proves the method serves retrace-free end-to-end
        for xop, xmethod in (("serve_trace_delora", "delora"),
                             ("serve_trace_hyperadapt", "hyperadapt")):
            xgrid = dict(SERVE_SHAPES["family"], method=xmethod)
            _, _, _, xreg, xeng = _build(backend, xgrid)
            entries.append(_replay_entry(xop, backend, mode, xgrid,
                                         cfg, xreg, xeng))

        # --- fused decode step, all slots active ----------------------
        state = _saturated_state(engine, grid)
        us_step = time_us(engine._step_fn, engine.params, registry.bank,
                          state, iters=iters or 10, reps=3)
        entries.append(dict(
            op="serve_decode_step", backend=backend, kind="decode",
            what="fused_step", mode=mode,
            shape=dict(batch=grid["slots"], tokens=1, d=d),
            us_per_call=round(us_step, 2)))

        # --- healthy-path guard gate: gated vs ungated step -----------
        # the ungated control jits the SAME step body but drops the
        # non-finite flag output, so XLA dead-code-eliminates the
        # sampled-logit gather + isfinite — exactly the pre-guard step.
        # Acceptance (jnp serving rows): gated/ungated ≤ 1.05; a ~700us
        # step needs longer samples than the other pairs for a 5% gate
        # on a small box (4x iters, 9 pairs), and the one-sided bound
        # gates on a low pair quantile (q — see _paired_us).
        ungated = jax.jit(
            lambda p, bk, st: engine._step_impl(p, bk, st)[:2])
        us_gated, _, r_guard = _paired_us(
            lambda: engine._step_fn(engine.params, registry.bank, state),
            lambda: ungated(engine.params, registry.bank, state),
            iters=4 * (iters or 10), pairs=9, q=0.25)
        entries.append(dict(
            op="serve_guard_overhead", backend=backend, kind="decode",
            what="nonfinite_guard", mode=mode,
            shape=dict(batch=grid["slots"], tokens=1, d=d),
            us_per_call=round(us_gated, 2)))
        derived[f"guard_vs_ungated_{backend}"] = round(r_guard, 3)

        # --- sharded-path tax: mesh-1x1 engine vs the plain engine ----
        # same engine, same grid, but constructed over a trivial 1x1
        # device mesh — everything the sharded path adds (NamedSharding
        # placement, sharding constraints on the slot state, out-
        # sharded bank swaps) with zero actual communication.  The
        # acceptance gates the pair ratio at ≤ 1.05 on jnp serving
        # rows: DESIGN.md §14's "sharding machinery is free when the
        # mesh is trivial" claim, measured like the guard gate.
        from repro.launch.mesh import make_host_mesh
        _, _, _, sreg2, seng2 = _build(backend, grid,
                                       mesh=make_host_mesh(1, 1))
        seng2.warmup()
        state_sh = _saturated_state(seng2, grid)
        us_sh, _, r_sh = _paired_us(
            lambda: seng2._step_fn(seng2.params, sreg2.bank, state_sh),
            lambda: engine._step_fn(engine.params, registry.bank, state),
            iters=4 * (iters or 10), pairs=9, q=0.25)
        entries.append(dict(
            op="serve_sharded_overhead", backend=backend, kind="decode",
            what="mesh1x1_vs_plain", mode=mode,
            shape=dict(batch=grid["slots"], tokens=1, d=d),
            us_per_call=round(us_sh, 2)))
        derived[f"sharded_vs_plain_{backend}"] = round(r_sh, 3)

        # --- prefill-into-slot admission ------------------------------
        b = grid["buckets"][-1]
        tokens = np.zeros((1, b), np.int32)
        us_pf = time_us(
            lambda: engine._prefill_fns[b](
                engine.params, registry.bank, engine._state, tokens,
                int(b // 2), int(0), int(0), int(grid["gen"])),
            iters=iters or 10, reps=3)
        entries.append(dict(
            op="serve_prefill_slot", backend=backend, kind="prefill",
            what=f"bucket{b}", mode=mode,
            shape=dict(batch=1, tokens=b, d=d),
            us_per_call=round(us_pf, 2)))

        # --- tenant churn: functional bank-row swap -------------------
        tree = registry.adapters_for(grid["universe"] - 1)
        us_swap = time_us(registry._swap, registry.bank, tree,
                          jnp.int32(0), iters=iters or 10, reps=3)
        entries.append(dict(
            op="tenant_churn", backend=backend, kind="swap",
            what="onboard", mode=mode,
            shape=dict(batch=1, tokens=1, d=d),
            us_per_call=round(us_swap, 2)))

        # --- merged single-tenant baseline at the same batch width ----
        merged = merge_params(params, registry.bank.select(0), peft)
        pf_m, st_m = make_serving_fns(cfg, None, grid["gen"])
        batch = {"tokens": jnp.zeros((grid["slots"], b), jnp.int32)}
        cache, tok = pf_m(merged, None, batch, None)
        _, us_merged, r_bm = _paired_us(
            lambda: engine._step_fn(engine.params, registry.bank, state),
            lambda: st_m(merged, None, cache, tok, None)[0],
            iters=iters or 10)
        entries.append(dict(
            op="serve_merged_step", backend=backend, kind="decode",
            what="merged_baseline", mode=mode,
            shape=dict(batch=grid["slots"], tokens=1, d=d),
            us_per_call=round(us_merged, 2)))
        derived[f"bank_vs_merged_overhead_{backend}"] = round(r_bm, 3)

        # --- tiered grid: merged hot tier vs pure-bank control --------
        tname = "tiered" if grid_name == "serving" else "tiered_tiny"
        tgrid = dict(SERVE_SHAPES[tname])
        zipfs, shift = tgrid.pop("zipf"), tgrid.pop("shift_hot_at")
        for a in zipfs:
            wl = dict(zipf_a=a, hot_permutation=tgrid["hot_permutation"])
            rows, ratio, treg_hot, teng = _tiered_pair(
                backend, mode, tgrid, cfg,
                reps=10 if backend == "jnp" else 2,
                what=f"zipf{a}", wl_kwargs=wl)
            entries += rows
            derived[f"tiered_vs_bank_zipf{a}_{backend}"] = ratio
        # mid-trace hot-set shift: one replay exercising promotion AND
        # demotion/eviction (still zero retraces)
        _, _, _, sreg, seng = _build(backend, tgrid)
        entries.append(_replay_entry(
            "serve_trace_tiered", backend, mode, tgrid, cfg, sreg, seng,
            what="hotshift",
            wl_kwargs=dict(zipf_a=max(zipfs),
                           hot_permutation=tgrid["hot_permutation"],
                           shift_hot_at=shift)))

        # --- hot-tier step floor: merged-tree decode at full batch ----
        tree = jax.block_until_ready(treg_hot.merge_tree(0))
        state_h = _saturated_state(teng, tgrid)
        tb = tgrid["buckets"][-1]
        pf_t, st_t = make_serving_fns(cfg, None, tgrid["gen"])
        cache_t, tok_t = pf_t(tree, None,
                              {"tokens": jnp.zeros((tgrid["slots"], tb),
                                                   jnp.int32)}, None)
        # same one-sided ≤1.05 gate as the guard pair: true ratio ~1.0,
        # so gate on the low pair quantile with long samples
        us_hot, _, r_hm = _paired_us(
            lambda: teng._merged_step_fn(tree, state_h),
            lambda: st_t(tree, None, cache_t, tok_t, None)[0],
            iters=4 * (iters or 10), pairs=9, q=0.25)
        entries.append(dict(
            op="serve_hot_step", backend=backend, kind="decode",
            what="merged_tier_step", mode=mode,
            shape=dict(batch=tgrid["slots"], tokens=1, d=d),
            us_per_call=round(us_hot, 2)))
        derived[f"hot_vs_merged_step_{backend}"] = round(r_hm, 3)

        # --- degraded-mode grid: one replay per fault class -----------
        entries += _degraded_entries(backend, mode, grid, cfg, derived)

        # --- crash safety: WAL overhead + warm-restart RTO ------------
        entries += _crash_safety_entries(backend, mode, grid, cfg,
                                         derived)

        # --- mesh-sharded scaling grid (subprocess, jnp) --------------
        # fake CPU devices only: on a chip host the child could not get
        # the chip this process holds (common/subproc.run_subprocess)
        if jax.default_backend() != "tpu":
            entries += _sharded_entries(backend, mode, grid_name, cfg,
                                        derived)

        if shapes == "serving" and backend == "jnp":
            # acceptance contract (jnp rows, full grid only — the tiny
            # CI smoke gates on --compare instead, where the noise
            # floor absorbs small-box jitter):
            #   hot-tier decode within 5% of the static merged step,
            #   tiered replay strictly faster than pure bank at
            #   zipf 1.1, and no >5% regression at uniform traffic —
            #   both replay checks on the paired-median ratio, the
            #   drift-immune estimator (_tiered_pair docstring)
            checks = [
                ("hot_vs_merged_step", derived["hot_vs_merged_step_jnp"]
                 <= 1.05),
                ("tiered>bank @zipf1.1",
                 derived["tiered_vs_bank_zipf1.1_jnp"] > 1.0),
                ("tiered>=0.95*bank @uniform",
                 derived["tiered_vs_bank_zipf0.0_jnp"] >= 0.95),
                # DESIGN.md §12: the in-jit non-finite guard must be
                # free on the healthy path...
                ("guard<=1.05x ungated",
                 derived["guard_vs_ungated_jnp"] <= 1.05),
                # ...and every fault class must complete its replay
                # with bounded overhead vs the healthy twin (wall
                # clock; generous bound — correctness rows, not perf)
                *[(f"degraded {c} <=3x healthy",
                   derived[f"degraded_overhead_{c}_jnp"] <= 3.0)
                  for c in ("corrupt", "kernel", "merge", "straggler",
                            "evict_storm")],
                # DESIGN.md §13: the write-ahead journal must be
                # near-free on the healthy path (batched fsync)
                ("journal<=1.05x plain",
                 derived["journal_vs_plain_jnp"] <= 1.05),
                # DESIGN.md §14: a trivial 1x1 mesh must not tax the
                # fused step — the sharded path is pure bookkeeping
                # until the mesh actually has >1 device
                ("sharded<=1.05x plain",
                 derived["sharded_vs_plain_jnp"] <= 1.05),
            ]
            failed = [name for name, ok in checks if not ok]
            if failed:
                raise SystemExit(
                    f"tiered-serving acceptance failed: {failed} "
                    f"(derived={derived})")

    covered = {(e["op"], e["backend"]) for e in entries}
    missing = sorted({(op, be) for op in ROW_OPS
                      for be in ("jnp", "pallas")} - covered)
    if missing:
        raise SystemExit(f"serve bench suite is missing entries for: "
                         f"{missing}")
    return dict(
        suite="serve", shapes=shapes, platform=jax.default_backend(),
        jax=jax.__version__,
        arch=dict(main="smollm-360m/smoke",
                  serve_trace_mamba2="mamba2-1.3b/smoke",
                  serve_trace_rglru="rglru-smoke (pure rglru pattern)",
                  serve_trace_hybrid="recurrentgemma-9b/smoke"),
        grids={k: {kk: list(vv) if isinstance(vv, tuple) else vv
                   for kk, vv in g.items()}
               for k, g in SERVE_SHAPES.items()},
        note=("pallas rows off-TPU are interpret-mode emulation at the "
              "tiny grid; jnp rows are the CPU-comparable numbers; "
              "serve_trace* us_per_call = 1e6/throughput_tok_s; "
              "serve_trace_{mamba2,rglru,hybrid} replay the recurrent "
              "families at the 'family' grid (pad-invariant prefill, "
              "DESIGN.md §10)"),
        derived=derived,
        entries=entries,
    )


if __name__ == "__main__":
    import json
    print(json.dumps(run_suite(shapes="tiny"), indent=1))
