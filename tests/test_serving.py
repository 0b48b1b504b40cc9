"""Continuous-batching serve subsystem (DESIGN.md §9).

Covers registry semantics (LRU eviction order, free-slot reuse, pin
protection, slot-update purity), the host-side tenant-id validation
guard, per-slot cursor decode, and — the load-bearing property — that
the slotted engine's continuous-batched output matches the one-shot
``_timed_generation`` path token-for-token per request with admissions
and retirements happening mid-flight, without a single jit retrace
after warmup.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, peft_targets
from repro.core.peft import (AdapterBank, init_adapter_bank, init_adapters,
                             validate_tenant_ids)
from repro.core.transforms import PEFTConfig
from repro.models import init_model
from repro.serving import (AdapterRegistry, Request, Scheduler, ServeEngine,
                           SlotAllocator, synthetic_workload)

RNG = jax.random.PRNGKey(0)

TINY_W = jax.random.normal(jax.random.fold_in(RNG, 9), (16, 16))
TINY_PARAMS = {"q_proj": {"kernel": TINY_W}}
TINY_PEFT = PEFTConfig(method="ether", n_blocks=4, targets="q_proj")


def tiny_registry(capacity, n_tenants=None):
    return AdapterRegistry(TINY_PARAMS, TINY_PEFT, capacity,
                           n_tenants=n_tenants, rng=RNG)


# ---------------------------------------------------------------------------
# validate_tenant_ids (frontend guard)
# ---------------------------------------------------------------------------

def test_validate_tenant_ids_raises_instead_of_clamping():
    with pytest.raises(ValueError, match=r"\[4\]"):
        validate_tenant_ids([0, 4], 4)          # would clamp to tenant 3
    with pytest.raises(ValueError):
        validate_tenant_ids([-1], 4)
    with pytest.raises(TypeError):
        validate_tenant_ids([0.5], 4)
    out = validate_tenant_ids(jnp.arange(3), 4)
    assert out.dtype == np.int32 and out.tolist() == [0, 1, 2]


def test_validate_tenant_ids_rejects_tracers():
    with pytest.raises(TypeError, match="host-side"):
        jax.jit(lambda i: validate_tenant_ids(i, 4))(jnp.arange(2))


# ---------------------------------------------------------------------------
# AdapterBank capacity / slot swap
# ---------------------------------------------------------------------------

def test_with_capacity_pads_tenant_axis():
    bank = init_adapter_bank(RNG, TINY_PARAMS, TINY_PEFT, 2)
    big = bank.with_capacity(5)
    assert big.tenants == 5
    u = big.tree["q_proj"]["u"]
    assert u.shape[0] == 5
    np.testing.assert_array_equal(u[:2], bank.tree["q_proj"]["u"])
    np.testing.assert_array_equal(u[2:], 0)     # zero rows = identity
    with pytest.raises(ValueError):
        bank.with_capacity(1)


def test_replace_slot_is_functional_and_row_local():
    bank = init_adapter_bank(RNG, TINY_PARAMS, TINY_PEFT, 3)
    before = jax.tree_util.tree_map(np.asarray, bank.tree)
    tree = init_adapters(jax.random.fold_in(RNG, 42), TINY_PARAMS,
                         TINY_PEFT)
    bank2 = bank.replace_slot(jnp.int32(1), tree)
    # old bank untouched (purity)
    jax.tree_util.tree_map(np.testing.assert_array_equal, before,
                           jax.tree_util.tree_map(np.asarray, bank.tree))
    u2 = bank2.tree["q_proj"]["u"]
    np.testing.assert_array_equal(u2[1], tree["q_proj"]["u"])
    np.testing.assert_array_equal(u2[0], before["q_proj"]["u"][0])
    np.testing.assert_array_equal(u2[2], before["q_proj"]["u"][2])


# ---------------------------------------------------------------------------
# AdapterRegistry: LRU, pins, free-slot reuse
# ---------------------------------------------------------------------------

def test_registry_lru_eviction_order():
    reg = tiny_registry(2)
    s0, s1 = reg.acquire(10), reg.acquire(11)
    reg.release(10), reg.release(11)
    reg.acquire(10)                              # refresh 10's recency
    reg.release(10)
    s2 = reg.acquire(12)                         # evicts 11 (LRU), not 10
    assert s2 == s1
    assert set(reg.resident()) == {10, 12}
    assert reg.stats["evictions"] == 1
    assert reg.acquire(10) == s0                 # still-warm hit
    assert reg.stats["hits"] == 2


def test_registry_never_evicts_pinned_tenants():
    reg = tiny_registry(1)
    reg.acquire(7)                               # pinned (in flight)
    with pytest.raises(RuntimeError, match="pinned"):
        reg.acquire(8)
    reg.release(7)
    assert reg.acquire(8) == 0                   # slot 0 reused
    assert reg.stats["evictions"] == 1


def test_registry_free_slot_reuse_and_swap_compiles_once():
    reg = tiny_registry(2, n_tenants=32)
    for t in range(8):                           # 4 full churn cycles
        reg.acquire(t)
        reg.release(t)
    assert set(reg.resident().values()) <= {0, 1}
    assert reg.stats["swap_traces"] == 1         # one compile, 8 swaps
    assert reg.stats["swaps"] == 8
    with pytest.raises(ValueError):
        reg.acquire(32)                          # outside the universe


def test_registry_release_without_acquire_raises():
    reg = tiny_registry(1)
    with pytest.raises(ValueError):
        reg.release(3)


def test_registry_put_refreshes_resident_row():
    reg = tiny_registry(2)
    slot = reg.acquire(5)
    tree = init_adapters(jax.random.fold_in(RNG, 5), TINY_PARAMS,
                         TINY_PEFT)
    custom = jax.tree_util.tree_map(lambda x: x + 1.0, tree)
    reg.put(5, custom)
    got = jnp.take(reg.bank.tree["q_proj"]["u"], slot, axis=0)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(custom["q_proj"]["u"]))


def test_slot_allocator_reuse_and_double_free():
    alloc = SlotAllocator(2)
    a, b = alloc.alloc(), alloc.alloc()
    assert {a, b} == {0, 1} and alloc.alloc() is None
    alloc.free(a)
    assert alloc.alloc() == a                    # freed slot reused
    with pytest.raises(ValueError):
        alloc.free(b), alloc.free(b)


# ---------------------------------------------------------------------------
# ServeEngine: continuous batching vs one-shot oracle, retrace freedom
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One smoke model served through the engine: 9 requests over 3
    slots / capacity-3 bank / 8-tenant universe (forces churn), plus
    the warmup trace snapshot and registry for assertions."""
    cfg = get_config("smollm-360m", "smoke")
    peft = PEFTConfig(method="ether", n_blocks=4,
                      targets=peft_targets("smollm-360m"), backend="jnp")
    params = init_model(RNG, cfg)
    reg = AdapterRegistry(params, peft, capacity=3, n_tenants=8,
                          rng=jax.random.fold_in(RNG, 1))
    eng = ServeEngine(cfg, params, reg, peft, slots=3,
                      prompt_buckets=(8, 16), max_new_tokens=8)
    snap = eng.warmup()
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, tenant_id=int(rng.integers(0, 8)),
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(3, 15)))
                    .astype(np.int32),
                    max_new_tokens=int(rng.integers(1, 9)))
            for i in range(9)]
    done = Scheduler(eng).run(copy.deepcopy(reqs),
                              clock=lambda: float("inf"))
    return dict(cfg=cfg, peft=peft, params=params, reg=reg, eng=eng,
                snap=snap, reqs=reqs, done=done)


def test_engine_completes_all_requests_with_slot_reuse(served):
    done = served["done"]
    assert len(done) == len(served["reqs"])
    assert {r.slot for r in done} <= {0, 1, 2}   # 9 requests, 3 slots
    for r in done:
        assert len(r.tokens) == r.max_new_tokens


def test_engine_never_retraces_after_warmup(served):
    served["eng"].assert_no_retrace(served["snap"])
    assert all(v == 1 for v in served["eng"].jit_cache_misses().values())


def test_engine_churned_tenants_mid_flight(served):
    stats = served["reg"].stats
    n_distinct = len({r.tenant_id for r in served["reqs"]})
    assert n_distinct > served["reg"].capacity
    assert stats["evictions"] > 0 and stats["misses"] > 3


def test_engine_matches_one_shot_oracle_token_for_token(served):
    """Continuous-batched output == the one-shot _timed_generation path
    (B=1, exact prompt length, same tenant adapters) per request."""
    from repro.launch.serve import _timed_generation, make_serving_fns
    cfg, peft, params = (served[k] for k in ("cfg", "peft", "params"))
    by_rid = {r.rid: r for r in served["done"]}
    pf, st = make_serving_fns(cfg, peft, 8)
    ids = np.zeros(1, np.int32)
    for req in served["reqs"]:
        bank1 = AdapterBank.stack(
            [served["reg"].adapters_for(req.tenant_id)], params, peft)
        batch = {"tokens": jnp.asarray(req.prompt)[None]}
        _, _, toks = _timed_generation(pf, st, params, bank1, batch,
                                       req.max_new_tokens - 1,
                                       tenant_ids=ids)
        assert by_rid[req.rid].tokens == toks[0].tolist(), req.rid


def test_engine_rejects_bad_requests(served):
    eng = served["eng"]
    with pytest.raises(ValueError):              # tenant outside universe
        eng.admit(Request(rid=99, tenant_id=8,
                          prompt=np.arange(4, dtype=np.int32),
                          max_new_tokens=2))
    with pytest.raises(ValueError, match="bucket"):
        eng.admit(Request(rid=99, tenant_id=0,
                          prompt=np.zeros(17, np.int32),
                          max_new_tokens=2))
    with pytest.raises(ValueError):
        eng.admit(Request(rid=99, tenant_id=0,
                          prompt=np.zeros(0, np.int32),
                          max_new_tokens=2))
    assert eng.n_free == eng.slots               # nothing leaked


def test_engine_windowed_attention_and_unscanned_layers():
    """local_attn (ring-layout trim in the slot write) + scan_layers
    off (batch axis 0 cache leaves) — both off the smoke default path —
    still match the one-shot oracle; ring-buffer wrap is rejected."""
    from repro.launch.serve import _timed_generation, make_serving_fns
    from repro.models.backbone import ModelConfig
    cfg = ModelConfig(name="win-smoke", n_layers=2, d_model=64, n_heads=2,
                      n_kv=1, d_ff=128, vocab=128,
                      block_pattern=("attn", "local_attn"), window=48,
                      scan_layers=False)
    peft = PEFTConfig(method="ether", n_blocks=4, targets="q_proj|o_proj",
                      backend="jnp")
    params = init_model(RNG, cfg)
    reg = AdapterRegistry(params, peft, 2, n_tenants=4,
                          rng=jax.random.fold_in(RNG, 2))
    eng = ServeEngine(cfg, params, reg, peft, slots=2,
                      prompt_buckets=(16,), max_new_tokens=6)
    snap = eng.warmup()
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, tenant_id=int(rng.integers(0, 4)),
                    prompt=rng.integers(0, 128, int(rng.integers(3, 15)))
                    .astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 7)))
            for i in range(3)]
    done = Scheduler(eng).run(copy.deepcopy(reqs),
                              clock=lambda: float("inf"))
    eng.assert_no_retrace(snap)
    pf, st = make_serving_fns(cfg, peft, 6)
    by = {r.rid: r for r in done}
    for r in reqs:
        bank1 = AdapterBank.stack([reg.adapters_for(r.tenant_id)],
                                  params, peft)
        _, _, toks = _timed_generation(
            pf, st, params, bank1,
            {"tokens": jnp.asarray(r.prompt)[None]},
            r.max_new_tokens - 1, tenant_ids=np.zeros(1, np.int32))
        assert by[r.rid].tokens == toks[0].tolist(), r.rid
    with pytest.raises(NotImplementedError, match="wrap"):
        ServeEngine(cfg, params, reg, peft, slots=2,
                    prompt_buckets=(48,), max_new_tokens=8)


def test_engine_decode_folds_bank_gemm_rows_and_prefill_does_not():
    """The bank decode step (one row per slot) runs the bank
    reflect-GEMM's folded grid; the prefill (a bucket of rows per
    sequence) keeps the per-sequence grid.  Trace-time counters."""
    from repro.core import execute
    from repro.models.backbone import ModelConfig
    cfg = ModelConfig(name="fold-smoke", n_layers=2, d_model=64, n_heads=2,
                      n_kv=1, d_ff=128, vocab=128)
    peft = PEFTConfig(method="ether", n_blocks=4, targets="q_proj|o_proj",
                      backend="pallas")
    params = init_model(RNG, cfg)
    reg = AdapterRegistry(params, peft, 2, n_tenants=4,
                          rng=jax.random.fold_in(RNG, 3))
    eng = ServeEngine(cfg, params, reg, peft, slots=2,
                      prompt_buckets=(16,), max_new_tokens=4)
    rows = "householder_gemm_batched.pallas_rows"
    execute.reset_counters()
    for rid, tenant in enumerate((1, 3)):
        eng.admit(Request(rid=rid, tenant_id=tenant,
                          prompt=np.arange(1, 9, dtype=np.int32),
                          max_new_tokens=4))
    prefill = execute.counters()
    assert prefill.get("householder_gemm_batched.pallas", 0) >= 2
    assert rows not in prefill
    execute.reset_counters()
    eng.step()
    decode = execute.counters()
    assert decode.get(rows, 0) >= 2                     # q_proj, o_proj
    assert decode[rows] == decode["householder_gemm_batched.pallas"]


def test_engine_backpressure_when_pinned_tenants_exceed_capacity():
    """More decode slots than bank capacity + all-distinct tenants: the
    scheduler must serialize on the registry (requeue + wait) instead
    of crashing the replay with 'all resident tenants pinned'."""
    from repro.models.backbone import ModelConfig
    cfg = ModelConfig(name="bp-smoke", n_layers=1, d_model=32, n_heads=1,
                      n_kv=1, d_ff=64, vocab=64, scan_layers=False)
    peft = PEFTConfig(method="ether", n_blocks=4, targets="q_proj",
                      backend="jnp")
    params = init_model(RNG, cfg)
    reg = AdapterRegistry(params, peft, capacity=1, n_tenants=4,
                          rng=jax.random.fold_in(RNG, 3))
    eng = ServeEngine(cfg, params, reg, peft, slots=3,
                      prompt_buckets=(8,), max_new_tokens=4)
    eng.warmup()
    reqs = [Request(rid=i, tenant_id=i,
                    prompt=np.full(4, i, np.int32), max_new_tokens=3)
            for i in range(4)]                   # 4 distinct, capacity 1
    done = Scheduler(eng).run(reqs, clock=lambda: float("inf"))
    assert len(done) == 4
    assert all(len(r.tokens) == 3 for r in done)
    assert reg.stats["evictions"] == 3           # serialized churn


def test_engine_rejects_oversized_generation(served):
    """A request whose decode would run past the slot's cache row must
    raise, not silently drop KV writes (OOB scatter) and emit garbage."""
    eng = served["eng"]
    with pytest.raises(ValueError, match="max_len"):
        eng.admit(Request(rid=98, tenant_id=0,
                          prompt=np.zeros(16, np.int32),
                          max_new_tokens=eng.max_len))
    assert eng.n_free == eng.slots


def test_engine_rejects_encdec_and_unknown_blocks():
    """Recurrent families are servable now (pad-invariant prefill,
    DESIGN.md §10); enc-dec and unknown block types still are not."""
    from repro.models import EncDecConfig
    from repro.models.backbone import ModelConfig
    params = {"stub": jnp.zeros(())}
    reg = tiny_registry(2)
    peft = PEFTConfig(method="ether", n_blocks=4, targets="q_proj")
    with pytest.raises(NotImplementedError, match="decoder-only"):
        ServeEngine(EncDecConfig(), params, reg, peft, slots=2)
    bogus = ModelConfig(name="bogus", block_pattern=("attn", "lstm"),
                        n_layers=2)
    with pytest.raises(NotImplementedError, match="unknown block"):
        ServeEngine(bogus, params, reg, peft, slots=2)


# ---------------------------------------------------------------------------
# Recurrent families: pad-invariant prefill in the slot engine
# ---------------------------------------------------------------------------

def _serve_vs_oracle(arch, *, buckets, gen, n_req=9, seed=7):
    """Replay a churning workload through the engine and compare every
    request token-for-token against the unpadded one-shot path."""
    from repro.launch.serve import _timed_generation, make_serving_fns
    cfg = get_config(arch, "smoke")
    peft = PEFTConfig(method="ether", n_blocks=4,
                      targets=peft_targets(arch), backend="jnp")
    params = init_model(RNG, cfg)
    reg = AdapterRegistry(params, peft, capacity=3, n_tenants=8,
                          rng=jax.random.fold_in(RNG, 1))
    eng = ServeEngine(cfg, params, reg, peft, slots=3,
                      prompt_buckets=buckets, max_new_tokens=gen)
    snap = eng.warmup()
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, tenant_id=int(rng.integers(0, 8)),
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(2,
                                                         buckets[-1] + 1)))
                    .astype(np.int32),
                    max_new_tokens=int(rng.integers(1, gen + 1)))
            for i in range(n_req)]
    done = Scheduler(eng).run(copy.deepcopy(reqs),
                              clock=lambda: float("inf"))
    eng.assert_no_retrace(snap)
    assert len(done) == n_req
    assert reg.stats["evictions"] > 0          # tenant churn mid-flight
    pf, st = make_serving_fns(cfg, peft, gen)
    by = {r.rid: r for r in done}
    for r in reqs:
        bank1 = AdapterBank.stack([reg.adapters_for(r.tenant_id)],
                                  params, peft)
        _, _, toks = _timed_generation(
            pf, st, params, bank1,
            {"tokens": jnp.asarray(r.prompt)[None]},
            r.max_new_tokens - 1, tenant_ids=np.zeros(1, np.int32))
        assert by[r.rid].tokens == toks[0].tolist(), \
            f"{arch} rid={r.rid} plen={len(r.prompt)}"


def test_engine_serves_mamba2_pad_invariant():
    """Pure-SSD model: prompts right-padded across two buckets, SSM
    state + conv tails streamed per slot — tokens must equal the
    unpadded one-shot oracle under mid-flight admit/retire/churn."""
    _serve_vs_oracle("mamba2-1.3b", buckets=(8, 16), gen=8)


def test_engine_serves_recurrentgemma_hybrid_pad_invariant():
    """Hybrid rglru/rglru/local_attn pattern (scanned units + recurrent
    remainder layers): RG-LRU hidden state, conv tails AND windowed KV
    live per slot; max_len stays within the window (no ring wrap)."""
    _serve_vs_oracle("recurrentgemma-9b", buckets=(8,), gen=8)


def test_prefill_true_lens_validated_host_side():
    """Satellite: the last-real-token gather is unclamped jax indexing —
    true_lens=0 would wrap to the last *padded* column and silently
    return pad logits; > S would clamp onto the wrong token.  Concrete
    bad lengths must raise at the frontend."""
    from repro.models import api, validate_true_lens
    from repro.models.backbone import ModelConfig
    cfg = ModelConfig(name="tl-smoke", n_layers=1, d_model=32, n_heads=1,
                      n_kv=1, d_ff=64, vocab=64, scan_layers=False)
    params = init_model(RNG, cfg)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    with pytest.raises(ValueError, match="true_lens"):
        api.prefill(params, None, batch, cfg, None,
                    true_lens=np.asarray([0, 4]))      # 0 → idx -1 wrap
    with pytest.raises(ValueError, match="true_lens"):
        api.prefill(params, None, batch, cfg, None,
                    true_lens=np.asarray([4, 9]))      # 9 > S=8
    with pytest.raises(TypeError):
        api.prefill(params, None, batch, cfg, None,
                    true_lens=np.asarray([1.5, 4.0]))  # non-integer
    _, ok = api.prefill(params, None, batch, cfg, None,
                        true_lens=np.asarray([1, 8]))  # bounds are legal
    assert ok.shape[0] == 2
    with pytest.raises(TypeError, match="host-side"):
        jax.jit(lambda t: validate_true_lens(t, 8))(jnp.asarray([3]))


def test_synthetic_workload_rejects_zero_rate():
    """Satellite: an explicit rate_rps=0 was falsy-coerced into the
    all-at-t=0 saturation mode; it must raise instead."""
    with pytest.raises(ValueError, match="rate_rps"):
        synthetic_workload(4, 2, vocab=64, rate_rps=0.0)
    with pytest.raises(ValueError, match="rate_rps"):
        synthetic_workload(4, 2, vocab=64, rate_rps=-1.0)
    w = synthetic_workload(4, 2, vocab=64, rate_rps=None)
    assert all(r.arrival_s == 0.0 for r in w)


def test_scheduler_drops_invalid_requests_instead_of_aborting():
    """Satellite: an over-long prompt / over-long generation must not
    kill a trace replay — the scheduler counts-and-drops it at
    admission and keeps serving, including through back-pressure (the
    bad request requeued while the engine is saturated still gets
    dropped, not looped forever)."""
    from repro.models.backbone import ModelConfig
    from repro.serving import summarize
    cfg = ModelConfig(name="drop-smoke", n_layers=1, d_model=32, n_heads=1,
                      n_kv=1, d_ff=64, vocab=64, scan_layers=False)
    peft = PEFTConfig(method="ether", n_blocks=4, targets="q_proj",
                      backend="jnp")
    params = init_model(RNG, cfg)
    reg = AdapterRegistry(params, peft, capacity=1, n_tenants=4,
                          rng=jax.random.fold_in(RNG, 4))
    eng = ServeEngine(cfg, params, reg, peft, slots=1, prompt_buckets=(8,),
                      max_new_tokens=4)
    eng.warmup()
    good = [Request(rid=i, tenant_id=i, prompt=np.full(4, i, np.int32),
                    max_new_tokens=3, arrival_s=0.0) for i in range(3)]
    bad = [
        # over-long prompt: no pad bucket fits (bucket_for raises)
        Request(rid=90, tenant_id=0, prompt=np.zeros(9, np.int32),
                max_new_tokens=2, arrival_s=0.0),
        # over-long generation: decode would run past the cache row
        Request(rid=91, tenant_id=1, prompt=np.zeros(8, np.int32),
                max_new_tokens=eng.max_len, arrival_s=0.0),
        # tenant outside the universe
        Request(rid=92, tenant_id=99, prompt=np.zeros(4, np.int32),
                max_new_tokens=2, arrival_s=0.0),
    ]
    # interleave so bad requests hit both a free and a saturated engine
    # (slots=1 ⇒ while rid=0 decodes, rid=90 waits in the queue first)
    reqs = [good[0], bad[0], good[1], bad[1], good[2], bad[2]]
    sched = Scheduler(eng)
    done = sched.run(reqs, clock=lambda: float("inf"))
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(len(r.tokens) == 3 for r in done)
    assert sorted(r.rid for r in sched.dropped) == [90, 91, 92]
    assert eng.n_free == eng.slots               # nothing leaked
    s = summarize(done, dropped=len(sched.dropped))
    assert s["n_requests"] == 3 and s["n_dropped"] == 3

    # only AdmissionError is shed: a bare ValueError out of admit is an
    # engine/registry invariant violation and must abort the replay
    class Broken:
        slots, n_free, n_active = 1, 1, 0

        def start_clock(self, t):
            pass

        def can_admit(self, req):
            return True

        def admit(self, req):
            raise ValueError("registry handed back a bad slot")

    broken = Scheduler(Broken())
    with pytest.raises(ValueError, match="bad slot"):
        broken.run([Request(rid=0, tenant_id=0,
                            prompt=np.zeros(2, np.int32),
                            max_new_tokens=1)],
                   clock=lambda: float("inf"))
    assert not broken.dropped


def test_poisson_zipf_workload_is_deterministic_and_in_range():
    w1 = synthetic_workload(16, 8, vocab=64, rate_rps=50.0, seed=3)
    w2 = synthetic_workload(16, 8, vocab=64, rate_rps=50.0, seed=3)
    assert [r.tenant_id for r in w1] == [r.tenant_id for r in w2]
    assert all(0 <= r.tenant_id < 8 for r in w1)
    assert all(r.arrival_s >= 0 for r in w1)
    arr = [r.arrival_s for r in w1]
    assert arr == sorted(arr) and arr[-1] > 0
    validate_tenant_ids([r.tenant_id for r in w1], 8)
