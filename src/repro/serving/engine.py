"""Continuous-batching serve engine over fixed decode slots.

The engine owns all device state for multi-tenant serving (DESIGN.md
§9): a preallocated cache with one row per decode *slot* — attention KV
plus, for recurrent blocks, the slot's SSM state (H,N,P), depthwise-conv
tails and RG-LRU hidden state (DESIGN.md §10) — a per-slot cursor vector
(each slot decodes at its own absolute position; recurrent state is
cursor-free), per-slot tenant-slot ids into the registry's
fixed-capacity :class:`~repro.core.peft.AdapterBank`, and per-slot
stop/length bookkeeping — all of it carried in a single pytree of FIXED
shapes.  Admission overwrites a slot's cache row wholesale (functional
zero-reset by construction: the prefilled B=1 row replaces every leaf),
so retired slots never leak state into the next request.

Three jitted entry points touch the device:

* ``prefill_into_slot`` (one compile per prompt pad bucket): run the
  padded prompt at batch 1, gather the last *real* token's logits
  (``true_lens`` prefill — recurrent blocks mask pad positions into
  identity state updates, so the streamed state equals the unpadded
  prompt's), scatter the padded cache into the slot's row, seed
  cursor/active/remaining/tenant for the slot, and sample the first
  token — all inside the jit.
* ``decode_step`` (one compile, ever): one fused batched greedy-decode
  step over ALL slots — adapter gather-and-reflect (the PR 2/3 batched
  kernels, untouched underneath), attention against per-slot cursors
  and the fused single-step ssd/rglru recurrences, argmax sampling,
  cursor/remaining/active updates.  Sampling lives inside the jit so
  measured step time is device work.
* ``decode_step_merged`` (one compile, ever): the *hot-tier* variant of
  the fused step (DESIGN.md §11) — same slot bookkeeping, but the
  weights are one hot tenant's fully-merged tree from the registry's
  :class:`~repro.core.peft.MergedCache` and NO adapter ops run.  Every
  merged tree shares the base params' leaf shapes, so which tenant it
  serves is a host-side argument pick, never a retrace.  :meth:`step`
  selects it whenever all active slots belong to a single merged-ready
  tenant; any mixed-tier batch runs the bank step (hot tenants stay
  bank-resident, so mixing is always correct).

Admission and retirement are therefore pure data: a new request writes
one cache row + four slot scalars (traced indices — no shape changes),
and retirement is host bookkeeping only.  Nothing retraces mid-flight;
every jitted function counts its traces (the python body runs only when
jax actually retraces), and :meth:`jit_cache_misses` exposes the counter
that ``--trace`` replays assert against after warmup.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.peft import validate_tenant_ids
from repro.models import api
from repro.models.backbone import ModelConfig
from repro.models.encdec import EncDecConfig
from repro.parallel.context import MeshContext, mesh_context
from repro.serving.registry import AdapterRegistry
from repro.serving.scheduler import (AdmissionError, Request, RequestError,
                                     SlotAllocator)

Params = dict[str, Any]

DEFAULT_BUCKETS = (16, 32)


def _check_servable(cfg, max_len: int) -> None:
    """The slot engine needs right-padded prefill to be exact per block
    family: causal masking hides pad KV for attention blocks, and
    recurrent blocks (ssd/rglru) run pad-invariant prefill — pad
    positions are identity state updates, so the per-slot state written
    at admission equals the unpadded prompt's state (DESIGN.md §10)."""
    if isinstance(cfg, EncDecConfig):
        raise NotImplementedError("serve engine is decoder-only")
    if getattr(cfg, "frontend", None) == "vision":
        raise NotImplementedError("serve engine does not support "
                                  "prepended frontend tokens")
    pattern = tuple(cfg.block_pattern) + tuple(cfg.remainder)
    bad = [b for b in pattern
           if b not in ("attn", "local_attn", "ssd", "rglru")]
    if bad:
        raise NotImplementedError(
            f"unknown block types {sorted(set(bad))}: the slot engine "
            f"serves attn/local_attn (causal pad masking) and ssd/rglru "
            f"(pad-invariant recurrent prefill) blocks")
    if ("local_attn" in pattern and cfg.window is not None
            and max_len > cfg.window):
        raise NotImplementedError(
            f"max_len {max_len} > window {cfg.window}: ring-buffer wrap "
            f"would expose stale pad KV to per-slot cursors")


class ServeEngine:
    """Fixed-slot continuous batching over a tenant adapter registry."""

    def __init__(self, cfg: ModelConfig, params: Params,
                 registry: AdapterRegistry, peft, *, slots: int = 8,
                 prompt_buckets=DEFAULT_BUCKETS, max_new_tokens: int = 32,
                 max_len: Optional[int] = None, faults=None,
                 step_retries: int = 1, journal=None, mesh=None,
                 replicas: Optional[int] = None):
        self.cfg, self.params, self.registry, self.peft = (cfg, params,
                                                           registry, peft)
        # write-ahead journal (DESIGN.md §13): admissions are journaled
        # BEFORE their prefill dispatches, every emitted token with its
        # tier, and terminal outcomes — enough to rebuild in-flight
        # requests as extended prefills after a process death.  None
        # (production-unjournaled / bench baseline) short-circuits
        # every hook.
        self._journal = journal
        # degradation knobs (DESIGN.md §12): a step dispatch that raises
        # (XLA/Pallas runtime failure) is retried `step_retries` times
        # before the whole active batch is failed with typed outcomes;
        # `faults` is an optional FaultPlan consulted at the step
        # boundary (None — production — short-circuits every hook)
        if step_retries < 0:
            raise ValueError("step_retries must be >= 0")
        self.step_retries = int(step_retries)
        self._faults = faults
        self._step_ordinal = 0
        self.fault_stats = dict(step_retries=0, step_failures=0,
                                nonfinite_slots=0, cancels=0)
        self.slots = int(slots)
        self.prompt_buckets = tuple(sorted({int(b) for b in prompt_buckets}))
        if not self.prompt_buckets or self.prompt_buckets[0] < 1:
            raise ValueError("need at least one positive prompt bucket")
        self.max_new_tokens = int(max_new_tokens)
        self.max_len = int(max_len or
                           (self.prompt_buckets[-1] + self.max_new_tokens))
        if self.prompt_buckets[-1] + self.max_new_tokens > self.max_len:
            raise ValueError(
                f"max_len {self.max_len} cannot hold a full bucket "
                f"({self.prompt_buckets[-1]}) + {self.max_new_tokens} "
                f"generated tokens")
        _check_servable(cfg, self.max_len)

        # -- mesh placement (DESIGN.md §14) ----------------------------
        # decode is S=1, so sequence sharding is meaningless here;
        # head-sharded attention co-locates with the model-sharded
        # weights, and the slot caches follow spec_for_cache
        self.mesh = mesh
        self._ctx = (MeshContext(mesh, seq_shard=False)
                     if mesh is not None else None)
        self._state_shardings = None
        if self._ctx is not None:
            from repro.parallel.sharding import param_specs, to_shardings
            self.params = jax.device_put(
                params,
                to_shardings(param_specs(params, mesh, serve=True), mesh))
            # the registry must swap/merge against the SAME sharded base
            # tree: a merged tree mixing mesh-committed kernels with
            # dev0-committed untargeted leaves is an "incompatible
            # devices" error inside jit
            self.registry.attach_mesh(mesh, self.params)
        # -- replica-parallel slot groups (DESIGN.md §14) --------------
        # decode slots are independent (no cross-slot math), so slot
        # groups replicate over the data axes and each data shard runs
        # its group's decode locally.  Placement is pure host
        # bookkeeping, so `replicas` also works without a mesh
        # (single-device placement tests).
        n = int(replicas) if replicas is not None else (
            self._ctx.dp_size if self._ctx is not None else 1)
        if (self._ctx is not None and replicas is not None
                and n != self._ctx.dp_size):
            raise ValueError(
                f"replicas={n} disagrees with the mesh's data extent "
                f"{self._ctx.dp_size} — slot groups replicate over the "
                f"data axes, one group per data shard")
        if n < 1:
            raise ValueError("need at least one replica")
        if self.slots % n:
            raise ValueError(f"slots {self.slots} not divisible by "
                             f"{n} replicas")
        self.n_replicas = n
        self._spr = self.slots // n            # slots per replica group
        self._allocs = [SlotAllocator(self._spr) for _ in range(n)]
        if n > 1:
            self.registry.configure_regions(n)

        self._requests: dict[int, Request] = {}
        self._traces: dict[str, int] = {}
        self._origin = time.perf_counter()
        self._state = self._fresh_state()
        if self._ctx is not None:
            self._state_shardings = self._state_shardings_for(self._state)
            self._state = jax.device_put(self._state,
                                         self._state_shardings)
        self._step_fn = self._jit("decode_step", self._step_impl)
        self._merged_step_fn = self._jit("decode_step_merged",
                                         self._merged_step_impl)
        self._prefill_fns = {
            b: self._jit(f"prefill_p{b}", self._make_prefill(b))
            for b in self.prompt_buckets}
        self.tier_stats = dict(bank_steps=0, merged_steps=0,
                               bank_tokens=0, merged_tokens=0)

    # -- jit bookkeeping ----------------------------------------------

    def _jit(self, name: str, fn):
        """jit with a cache-miss counter: the wrapped python body runs
        only when jax (re)traces, so the count IS the compile count.
        Under a mesh every call runs inside the engine's mesh context so
        *tracing* sees the sharding policy (shard_heads /
        shard_slot_cache activate); on cache-hit calls the context entry
        is a cheap list push.  The program is named ``name``, so its XLA
        module reads ``jit_<name>`` in a profile."""
        def counted(*args):
            self._traces[name] = self._traces.get(name, 0) + 1
            return fn(*args)
        counted.__name__ = counted.__qualname__ = name
        jitted = jax.jit(counted)
        if self._ctx is None:
            return jitted

        def meshed(*args):
            with mesh_context(self._ctx):
                return jitted(*args)
        return meshed

    def jit_cache_misses(self, include_registry: bool = True
                         ) -> dict[str, int]:
        out = dict(self._traces)
        if include_registry:
            out["registry_swap"] = self.registry.stats.get("swap_traces", 0)
            out["registry_init"] = self.registry.stats.get("init_traces", 0)
            if getattr(self.registry, "merged_capacity", 0) > 0:
                out["registry_merge"] = self.registry.stats.get(
                    "merge_traces", 0)
        return out

    def _now(self) -> float:
        return time.perf_counter() - self._origin

    def _jrec(self, rec) -> None:
        if self._journal is not None:
            self._journal.append(rec)

    def start_clock(self, origin: float) -> None:
        """Align request timestamps with the scheduler's replay clock."""
        self._origin = origin

    # -- device state -------------------------------------------------

    def _fresh_state(self) -> Params:
        cache = api.init_cache(self.cfg, self.slots, self.max_len)
        cache["cursor"] = jnp.zeros((self.slots,), jnp.int32)
        state = dict(
            cache=cache,
            tok=jnp.zeros((self.slots, 1), jnp.int32),
            tenant=jnp.zeros((self.slots,), jnp.int32),
            active=jnp.zeros((self.slots,), bool),
            remaining=jnp.zeros((self.slots,), jnp.int32),
        )
        if self._state_shardings is not None:
            state = jax.device_put(state, self._state_shardings)
        return state

    def _state_shardings_for(self, state: Params):
        """NamedSharding tree for the slot state: cache leaves follow
        ``spec_for_cache`` (slots→data, one inner dim→model when
        divisible), the per-slot bookkeeping vectors follow the slot
        axis.  The jitted steps constrain their outputs to exactly this
        tree and eager host mutations re-pin through it, so the state's
        layout is a closed invariant — which is what keeps the jit
        signatures stable (zero retraces) under admit/retire churn."""
        from repro.parallel.sharding import (batch_specs, cache_specs,
                                             spec_for_batch, to_shardings)
        spec = {k: batch_specs(v, self.mesh)
                for k, v in state.items() if k != "cache"}
        cspec = cache_specs(state["cache"], self.mesh)
        cspec["cursor"] = spec_for_batch(
            "cursor", tuple(state["cache"]["cursor"].shape), self.mesh)
        spec["cache"] = cspec
        return to_shardings(spec, self.mesh)

    def _constrain(self, state: Params) -> Params:
        """Pin a jitted step's output state to the invariant layout
        (no-op unmeshed)."""
        if self._state_shardings is None:
            return state
        return jax.lax.with_sharding_constraint(state,
                                                self._state_shardings)

    def _pin(self, key: str, arr):
        """Re-commit an eagerly-mutated state leaf (``.at[].set`` runs
        OUTSIDE the jitted steps in the fail/cancel paths) to its
        invariant sharding — a drifted leaf layout would be a new input
        signature for the next step (a retrace)."""
        if self._state_shardings is None:
            return arr
        return jax.device_put(arr, self._state_shardings[key])

    def _step_impl(self, params, bank, state):
        """One fused batched decode step over all slots (argmax sampling
        inside the jit — ms/token measures device work only)."""
        cache = state["cache"]
        logits, new_cache = api.decode_step(
            params, bank, cache, state["tok"], self.cfg, self.peft,
            tenant_ids=state["tenant"])
        new_state, nxt, bad = self._advance(state, logits, new_cache)
        return self._constrain(new_state), nxt, bad

    def _merged_step_impl(self, merged_params, state):
        """Hot-tier decode step: every active slot belongs to ONE hot
        tenant whose reflection is already absorbed into
        ``merged_params`` (registry merged cache), so the step runs the
        plain backbone — zero per-token adapter work.  All merged trees
        share the base params' leaf shapes/dtypes, so this compiles once
        at warmup and serves ANY hot tenant without retracing; which
        tier (and which tenant's tree) runs is a host-side pick in
        :meth:`step` over host-known tier state, never a traced branch."""
        cache = state["cache"]
        logits, new_cache = api.decode_step(
            merged_params, None, cache, state["tok"], self.cfg, None,
            tenant_ids=None)
        new_state, nxt, bad = self._advance(state, logits, new_cache)
        return self._constrain(new_state), nxt, bad

    def _advance(self, state, logits, new_cache):
        """Shared slot bookkeeping for both step tiers (traced).

        Also computes the per-slot non-finite-logits flag HERE, inside
        the jit (DESIGN.md §12): finiteness of the SAMPLED logit — an
        O(slots) gather at the argmax the sampler already computed, not
        a second O(slots·vocab) pass.  ``jnp.argmax`` treats NaN as
        maximal, so any NaN in a row samples its NaN index; +Inf is
        sampled by construction; an all--Inf row gathers -Inf — the
        only rows the full-row reduce would additionally flag are
        partial--Inf rows with a finite max, and under greedy sampling
        those emit exactly the healthy argmax token (not degradation).
        The flags ride back with the sampled tokens in the same
        ``device_get`` — no extra kernel round-trip, no second host
        sync, and by construction no new compile (the trace counters
        prove it).  The flag is masked by ``active`` because inactive
        slots decode garbage by design — their drift must never
        quarantine anyone.  Batched decode is independent along the
        slot axis, so a NaN cannot cross slots: the flag identifies
        exactly the poisoned slot(s)."""
        cache = state["cache"]
        active = state["active"]
        with jax.named_scope("sample"):
            last = logits[:, -1]
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            sampled = jnp.take_along_axis(last, nxt[:, None], axis=-1)[:, 0]
            bad = active & ~jnp.isfinite(sampled)
        # inactive slots keep their cursor (their garbage KV write lands
        # on the same in-bounds position every step, and their recurrent
        # state drifts harmlessly — every cache leaf row is fully
        # overwritten by the next prefill-into-slot)
        new_cache["cursor"] = jnp.where(active, new_cache["cursor"],
                                        cache["cursor"])
        remaining = jnp.where(active, state["remaining"] - 1,
                              state["remaining"])
        return dict(
            cache=new_cache,
            tok=jnp.where(active, nxt, state["tok"][:, 0])[:, None],
            tenant=state["tenant"],
            active=active & (remaining > 0),
            remaining=remaining,
        ), nxt, bad

    def _make_prefill(self, bucket: int):
        def impl(params, bank, state, tokens, true_len, slot, tslot,
                 max_new):
            true_len = jnp.asarray(true_len, jnp.int32)
            slot = jnp.asarray(slot, jnp.int32)
            tslot = jnp.asarray(tslot, jnp.int32)
            max_new = jnp.asarray(max_new, jnp.int32)
            cache1, logits = api.prefill(
                params, bank, {"tokens": tokens}, self.cfg, self.peft,
                tenant_ids=tslot[None], true_lens=true_len[None])
            cache1 = api.pad_cache(cache1, self.cfg, self.max_len)
            tok = jnp.argmax(logits[0, -1]).astype(jnp.int32)
            # same in-jit non-finite guard as _advance (finiteness of
            # the SAMPLED logit), at the prefill boundary: a poisoned
            # tenant must be caught on its FIRST token (a 1-token
            # request never reaches a decode step)
            bad = ~jnp.isfinite(logits[0, -1, tok])
            cache = state["cache"]
            new_cache: Params = {"cursor": cache["cursor"].at[slot]
                                 .set(true_len)}
            for key, sub in cache.items():
                if key == "cursor":
                    continue
                ax = 1 if key.startswith("pos") else 0
                new_cache[key] = jax.tree_util.tree_map(
                    lambda big, small, _ax=ax: _write_row(big, small,
                                                          slot, _ax),
                    sub, cache1[key])
            remaining = state["remaining"].at[slot].set(max_new - 1)
            new_state = dict(
                cache=new_cache,
                tok=state["tok"].at[slot, 0].set(tok),
                tenant=state["tenant"].at[slot].set(tslot),
                active=state["active"].at[slot].set(max_new > 1),
                remaining=remaining,
            )
            return self._constrain(new_state), tok, bad
        return impl

    # -- serving API --------------------------------------------------

    @property
    def n_free(self) -> int:
        return sum(a.n_free for a in self._allocs)

    @property
    def n_active(self) -> int:
        return len(self._requests)

    # -- replica placement (DESIGN.md §14) ----------------------------

    def _alloc_slot(self, replica: int) -> Optional[int]:
        local = self._allocs[replica].alloc()
        return None if local is None else replica * self._spr + local

    def _free_slot(self, slot: int) -> None:
        r, local = divmod(slot, self._spr)
        self._allocs[r].free(local)

    def _replica_of(self, slot: int) -> int:
        return slot // self._spr

    def free_by_replica(self) -> list[int]:
        """Free decode slots per replica group (scheduler placement)."""
        return [a.n_free for a in self._allocs]

    def replicas_holding(self, tenant_id: int) -> tuple[int, ...]:
        """Replicas whose bank region already holds the tenant's
        adapter rows — admitting there costs zero swaps."""
        return self.registry.regions_holding(tenant_id)

    def can_admit_on(self, req: Request, replica: int) -> bool:
        """:meth:`can_admit`, scoped to one replica group: a slot is
        free in the group AND the tenant's rows are acquirable in the
        replica's bank region."""
        return (self._allocs[replica].n_free > 0
                and self.registry.can_acquire(req.tenant_id,
                                              region=replica))

    def _pick_replica(self, req: Request) -> int:
        """Self-placement when the scheduler did not choose: prefer a
        replica whose region already holds the tenant's rows (no swap),
        else any replica that can admit, else any with a free slot (so
        ``acquire`` raises the same typed errors as the single-replica
        path).  Least-loaded with lowest-id tie-break — deterministic
        for a fixed request sequence."""
        if self.n_replicas == 1:
            return 0
        free = self.free_by_replica()
        ok = [r for r in range(self.n_replicas)
              if free[r] > 0
              and self.registry.can_acquire(req.tenant_id, region=r)]
        holding = set(self.registry.regions_holding(req.tenant_id))
        cands = ([r for r in ok if r in holding] or ok
                 or [r for r in range(self.n_replicas) if free[r] > 0])
        if not cands:
            return 0            # nothing free anywhere: admit raises
        return min(cands, key=lambda r: (-free[r], r))

    def can_admit(self, req: Request) -> bool:
        """True iff :meth:`admit` would succeed right now: a decode slot
        is free AND the tenant's bank slot is acquirable (resident, or
        free/evictable) on the same replica.  With more decode slots
        than bank capacity, distinct-tenant requests beyond capacity
        must wait — the scheduler checks here and applies back-pressure
        instead of letting ``registry.acquire`` raise mid-replay."""
        return any(self.can_admit_on(req, r)
                   for r in range(self.n_replicas))

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prompt_buckets:
            if prompt_len <= b:
                return b
        raise AdmissionError(
            f"prompt length {prompt_len} exceeds the largest pad "
            f"bucket {self.prompt_buckets[-1]}")

    def ensure_bucket(self, prompt_len: int) -> int:
        """Guarantee a prefill pad bucket covering ``prompt_len`` exists,
        adding one if needed; returns the covering bucket.

        Recovery needs this (DESIGN.md §13): a resumed request's
        extended prefill runs over ``prompt + journaled tokens``, which
        can exceed every configured bucket.  New buckets are rounded up
        to a multiple of 8 (bounding the number of distinct compiles
        across resume lengths) and capped at ``max_len`` — always
        enough, because the original admission enforced
        ``plen + max_new - 1 <= max_len``.  MUST be called before
        :meth:`warmup` so the new bucket compiles there and post-warmup
        traffic stays retrace-free."""
        n = int(prompt_len)
        if not 1 <= n <= self.max_len:
            raise ValueError(f"prompt_len {n} outside [1, {self.max_len}]")
        if n <= self.prompt_buckets[-1]:
            return self.bucket_for(n)
        b = min(self.max_len, ((n + 7) // 8) * 8)
        self.prompt_buckets = tuple(sorted({*self.prompt_buckets, b}))
        self._prefill_fns[b] = self._jit(f"prefill_p{b}",
                                         self._make_prefill(b))
        return b

    def admit(self, req: Request,
              replica: Optional[int] = None) -> list[Request]:
        """Prefill ``req`` into a free slot (acquiring its tenant's bank
        slot from the registry) and emit its first token.  Returns the
        request in a list iff it finished immediately (1-token gen).
        ``replica`` pins the slot group (scheduler placement); None
        self-places via :meth:`_pick_replica`."""
        with jax.profiler.TraceAnnotation(
                "engine.admit", rid=req.rid, tenant=req.tenant_id,
                prompt_len=len(req.prompt)) as span:
            return self._admit(req, replica, span)

    def _admit(self, req: Request, replica: Optional[int],
               span) -> list[Request]:
        plen = int(len(req.prompt))
        if plen < 1:
            raise AdmissionError("empty prompt")
        if int(req.max_new_tokens) < 1:
            raise AdmissionError("max_new_tokens must be >= 1")
        if plen + int(req.max_new_tokens) - 1 > self.max_len:
            # the last decode write would land past the slot's cache row
            # and be silently dropped (jax out-of-bounds scatter), so
            # every later token would read a cache missing recent KV
            raise AdmissionError(
                f"prompt ({plen}) + max_new_tokens "
                f"({req.max_new_tokens}) - 1 exceeds the engine's "
                f"max_len {self.max_len}")
        bucket = self.bucket_for(plen)
        span.set_metadata(bucket=bucket)
        # host-side guard before the traced last-real-token gather: the
        # jitted prefill cannot validate its traced true_len itself.
        # Stays a bare ValueError — plen <= bucket is guaranteed by
        # bucket_for above, so a raise here is an engine bug, not a bad
        # request, and must NOT be shed as a drop.
        api.validate_true_lens(plen, bucket)
        if replica is None:
            replica = self._pick_replica(req)
        slot = self._alloc_slot(replica)
        if slot is None:
            raise RuntimeError("no free decode slot (check n_free first)")
        try:
            tslot = self.registry.acquire(req.tenant_id,   # validates id
                                          region=replica)
        except ValueError as e:
            self._free_slot(slot)                      # don't leak it
            # bad tenant id in the request → droppable rejection
            raise AdmissionError(str(e)) from e
        except Exception:
            self._free_slot(slot)
            raise
        # frontend guard on the *slot* indirection as well — a registry
        # bug must raise here, not clamp inside the bank gather
        validate_tenant_ids([tslot], self.registry.capacity)
        # write-ahead: the admission is journaled once it is certain to
        # reach the prefill dispatch (all validations passed, slot and
        # bank pin held) and BEFORE any device work — a crash anywhere
        # past this line re-admits the request as a resume; a crash
        # before it re-runs the request from the workload
        self._jrec({"t": "admit", "rid": int(req.rid),
                    "tid": int(req.tenant_id),
                    "p": [int(t) for t in np.asarray(req.prompt)],
                    "g": int(req.max_new_tokens),
                    "a": float(req.arrival_s)})
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = np.asarray(req.prompt, np.int32)
        t0 = self._now()
        with jax.profiler.TraceAnnotation("engine.prefill", bucket=bucket):
            state, tok, bad = self._prefill_fns[bucket](
                self.params, self.registry.bank, self._state, tokens,
                int(plen), int(slot), int(tslot), int(req.max_new_tokens))
            first, poisoned = jax.device_get((tok, bad))   # device sync
        self._state = state
        req.slot = slot
        req.admit_s = t0
        if bool(poisoned):
            # the tenant's adapters produced non-finite prefill logits:
            # quarantine BEFORE retiring (so the release inside _retire
            # sees the flag and runs the deferred two-tier eviction when
            # the last pin drops) and return the request with a typed
            # outcome instead of a garbage first token
            self._requests[slot] = req
            return [self._fail_slot(slot, RequestError(
                "nonfinite", f"tenant {req.tenant_id} produced "
                f"non-finite prefill logits"))]
        req.first_token_s = self._now()
        req.tokens.append(int(first))
        # prefill (and its first token) always runs the bank tier: hot
        # tenants are bank-resident too, and per-bucket merged prefill
        # variants would multiply compiles for a non-steady-state cost
        req.tiers.append("bank")
        self._jrec({"t": "tok", "rid": int(req.rid), "k": int(first),
                    "x": "bank"})
        self._requests[slot] = req
        if req.done:
            return [self._retire(slot)]
        return []

    def step(self) -> list[Request]:
        """One batched decode step; returns requests that finished.

        Tier pick (host-side, zero retraces): when every active slot
        belongs to ONE tenant whose merged entry is ready, the step runs
        the hot-tier merged weights (no adapter ops); any mixed-tenant
        batch — hot tenants included, they stay bank-resident — runs the
        bank step, bitwise identical to a tierless engine.  Each token
        records which tier produced it (``req.tiers``) so the oracle can
        replay the exact schedule (merged vs reflect-then-GEMM differ in
        rounding).

        Degradation (DESIGN.md §12): the FaultPlan hooks fire at this
        dispatch boundary (eviction storms, straggler delays, injected
        kernel raises); a dispatch that raises ``RuntimeError`` is
        retried up to ``step_retries`` times, then the whole active
        batch fails with typed ``kernel`` outcomes — one bad step must
        cost its in-flight requests, never the replay.  Slots whose
        non-finite flag fired are quarantined at retire time with typed
        ``nonfinite`` outcomes."""
        if not self._requests:
            return []
        with jax.profiler.TraceAnnotation(
                "engine.step", ordinal=self._step_ordinal,
                active=len(self._requests)) as span:
            return self._step(span)

    def _step(self, span) -> list[Request]:
        ordinal = self._step_ordinal
        self._step_ordinal += 1
        if self._faults is not None:
            # engine-step crash boundary (DESIGN.md §13): outside the
            # retry loop below and a BaseException — a process death is
            # not a kernel failure and must not be retried away
            self._faults.crash_now("step")
        if self._faults is not None and self._faults.storm_now(ordinal):
            # memory-pressure eviction storm: pins keep every in-flight
            # tenant resident, so the step below still serves correctly
            self.registry.flush_unpinned()
        tids = {r.tenant_id for r in self._requests.values()}
        merged = (self.registry.merged_for(next(iter(tids)))
                  if len(tids) == 1 else None)
        span.set_metadata(tier=int(merged is not None), tenants=len(tids))
        t0 = time.perf_counter()
        last_err = None
        for attempt in range(1 + self.step_retries):
            if attempt:
                self.fault_stats["step_retries"] += 1
            try:
                if self._faults is not None:
                    self._faults.on_step(ordinal)
                if merged is not None:
                    tier = "merged"
                    state, nxt, bad = self._merged_step_fn(merged,
                                                           self._state)
                else:
                    tier = "bank"
                    state, nxt, bad = self._step_fn(
                        self.params, self.registry.bank, self._state)
                # one fetch returns tokens AND non-finite flags — the
                # healthy path pays no second device sync for the guard
                with jax.profiler.TraceAnnotation("engine.step.fetch"):
                    toks, flags = jax.device_get((nxt, bad))
                break
            except RuntimeError as e:
                # XLA/Pallas runtime failure (InjectedFault models it)
                last_err = e
        else:
            return self._fail_batch(ordinal, last_err)
        dt = time.perf_counter() - t0
        self._state = state
        self.tier_stats[f"{tier}_steps"] += 1
        self.tier_stats[f"{tier}_tokens"] += len(self._requests)
        if self._journal is not None:
            # one batched record per step, BEFORE retirement bookkeeping
            # so token records always precede their request's terminal
            # record in the journal
            emitted = [[int(r.rid), int(toks[s])]
                       for s, r in self._requests.items() if not flags[s]]
            if emitted:
                self._jrec({"t": "step", "x": tier, "e": emitted})
        finished = []
        for slot, req in list(self._requests.items()):
            if flags[slot]:
                finished.append(self._fail_slot(slot, RequestError(
                    "nonfinite", f"tenant {req.tenant_id} produced "
                    f"non-finite logits", step=ordinal)))
                continue
            req.tokens.append(int(toks[slot]))
            req.tiers.append(tier)
            req.step_s.append(dt)
            if req.done:
                finished.append(self._retire(slot))
        return finished

    def _fail_slot(self, slot: int, error: RequestError) -> Request:
        """Quarantine path for a poisoned slot: mark the tenant suspect
        (two-tier eviction, deferred past its last pin), deactivate the
        slot on device so it stops burning decode work, and retire the
        request with its typed outcome."""
        req = self._requests[slot]
        req.error = error
        if error.kind == "nonfinite":
            self.fault_stats["nonfinite_slots"] += 1
            self.registry.mark_suspect(req.tenant_id)
        self._state["active"] = self._pin(
            "active", self._state["active"].at[slot].set(False))
        return self._retire(slot)

    def _fail_batch(self, ordinal: int, err) -> list[Request]:
        """Step retries exhausted: fail every in-flight request with a
        typed ``kernel`` outcome and reset the slot mask — the engine
        stays serviceable (state shapes untouched, nothing retraces) and
        the next admissions overwrite the dead rows wholesale."""
        self.fault_stats["step_failures"] += 1
        out = []
        for slot, req in list(self._requests.items()):
            req.error = RequestError("kernel", str(err), step=ordinal)
            out.append(self._retire(slot))
        self._state["active"] = self._pin(
            "active", jnp.zeros_like(self._state["active"]))
        self._state["remaining"] = self._pin(
            "remaining", jnp.zeros_like(self._state["remaining"]))
        return out

    def inflight(self) -> dict[int, Request]:
        """slot → in-flight request (scheduler watchdog introspection)."""
        return dict(self._requests)

    def cancel(self, slot: int, error: RequestError) -> Request:
        """Cancel one in-flight request with a typed outcome (watchdog /
        blown total deadline).  Host bookkeeping plus a single slot
        deactivation — no retrace, no effect on sibling slots."""
        if slot not in self._requests:
            raise ValueError(f"slot {slot} has no in-flight request")
        self.fault_stats["cancels"] += 1
        req = self._requests[slot]
        req.error = error
        self._state["active"] = self._pin(
            "active", self._state["active"].at[slot].set(False))
        return self._retire(slot)

    def preferred_tenant(self) -> Optional[int]:
        """Affinity hint for the scheduler: the most common hot-tier
        tenant among in-flight requests, else None.  Filling free slots
        with this tenant's queued requests converges the batch onto a
        single hot tenant, unlocking merged-tier steps — without it, a
        continuously-refilled mixed batch almost never collapses to one
        tenant and the merged cache sits idle."""
        counts: dict[int, int] = {}
        for r in self._requests.values():
            t = r.tenant_id
            if self.registry.is_merged(t):
                counts[t] = counts.get(t, 0) + 1
        return max(counts, key=lambda t: counts[t]) if counts else None

    def _retire(self, slot: int) -> Request:
        """Pure host bookkeeping: free the slot, unpin the tenant.  No
        device work — the slot's mask bit is already False and the next
        admission overwrites the row wholesale."""
        req = self._requests.pop(slot)
        self._free_slot(slot)
        self.registry.release(req.tenant_id,
                              region=self._replica_of(slot))
        req.finish_s = self._now()
        end = {"t": "end", "rid": int(req.rid),
               "ok": 1 if req.error is None else 0}
        if req.error is not None:
            end["err"] = req.error.kind
        self._jrec(end)
        return req

    def resume(self, req: Request) -> list[Request]:
        """Re-admit a crash-recovered in-flight request (DESIGN.md §13)
        as an **extended prefill** over ``prompt + journaled tokens``:
        the journal proves the pre-crash tokens, greedy decode makes
        the continuation deterministic, and the resume point is
        recorded (``req.resume_points``) so the recovery-schedule-
        faithful oracle can replay the exact prefill/decode boundary.
        Returns the request in a list iff it finished immediately —
        including the done-but-unrecorded case (every token journaled,
        the terminal record lost in the un-fsynced tail), which is
        retired on the spot without consuming a slot."""
        req.recovered = True
        k = len(req.tokens)
        if req.done:
            req.admit_s = req.admit_s if req.admit_s is not None else 0.0
            req.first_token_s = req.first_token_s or req.admit_s
            req.finish_s = self._now()
            self._jrec({"t": "end", "rid": int(req.rid), "ok": 1})
            return [req]
        eff = np.concatenate([np.asarray(req.prompt, np.int32),
                              np.asarray(req.tokens, np.int32)])
        plen = int(len(eff))
        remaining = int(req.max_new_tokens) - k
        bucket = self.bucket_for(plen)    # ensure_bucket ran pre-warmup
        api.validate_true_lens(plen, bucket)
        replica = self._pick_replica(req)
        slot = self._alloc_slot(replica)
        if slot is None:
            raise RuntimeError("no free decode slot for resume (at most "
                               "`slots` requests were in flight at the "
                               "crash, so this is a recovery bug)")
        try:
            tslot = self.registry.acquire(req.tenant_id, region=replica)
        except Exception:
            self._free_slot(slot)
            raise
        validate_tenant_ids([tslot], self.registry.capacity)
        self._jrec({"t": "resume", "rid": int(req.rid), "n": k})
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = eff
        t0 = self._now()
        state, tok, bad = self._prefill_fns[bucket](
            self.params, self.registry.bank, self._state, tokens,
            plen, int(slot), int(tslot), remaining)
        first, poisoned = jax.device_get((tok, bad))   # device sync
        self._state = state
        req.slot = slot
        req.admit_s = t0
        req.resume_points.append(k)
        self._requests[slot] = req
        if bool(poisoned):
            return [self._fail_slot(slot, RequestError(
                "nonfinite", f"tenant {req.tenant_id} produced "
                f"non-finite logits on resume"))]
        req.resumed_s = self._now()
        if req.first_token_s is None:
            req.first_token_s = req.resumed_s
        req.tokens.append(int(first))
        req.tiers.append("bank")          # extended prefill = bank tier
        self._jrec({"t": "tok", "rid": int(req.rid), "k": int(first),
                    "x": "bank"})
        if req.done:
            return [self._retire(slot)]
        return []

    def warmup(self) -> dict[str, int]:
        """Compile every jitted entry point (all pad buckets, the decode
        step, the registry's row swap + synthetic-adapter init) on
        throwaway state, then reset.  Returns the trace-counter snapshot
        that traffic is asserted against."""
        scratch = self._state
        for b in self.prompt_buckets:
            tokens = np.zeros((1, b), np.int32)
            state, _, _ = self._prefill_fns[b](
                self.params, self.registry.bank, scratch, tokens,
                int(1), int(0), int(0), int(2))
        state, _, _ = self._step_fn(self.params, self.registry.bank, state)
        # the merged-tier step: base params share every leaf shape/dtype
        # with a merged tree, so this one compile covers every future
        # hot tenant — promotions/demotions mid-trace never retrace
        state2, _, _ = self._merged_step_fn(self.params, state)
        jax.block_until_ready(state2["tok"])
        # the fail/cancel paths mask a slot off eagerly (_fail_slot)
        jax.block_until_ready(
            self._pin("active", state["active"].at[0].set(False)))
        self.registry.warm_scrub()                     # quarantine scrub
        self.registry.warm_init()                      # warms init_fn
        self.registry.warm_swap()                      # warms _swap
        self.registry.warm_merge()                     # warms _merge
        self._state = self._fresh_state()
        return self.jit_cache_misses()

    def assert_no_retrace(self, snapshot: dict[str, int]) -> None:
        """Raise if any jitted serving function retraced since
        ``snapshot`` (taken at :meth:`warmup`)."""
        fresh = self.jit_cache_misses()
        grew = {k: (snapshot.get(k, 0), v) for k, v in fresh.items()
                if v > snapshot.get(k, 0)}
        if grew:
            raise AssertionError(
                f"jit cache misses after warmup — serving retraced "
                f"mid-flight: {grew}")


def _write_row(big, small, slot, batch_axis):
    """Scatter one prefilled request's cache leaf (batch size 1) into
    row ``slot`` of the engine's slotted cache leaf."""
    t_ax = big.ndim - 2                       # k/v time axis
    if small.shape[t_ax] > big.shape[t_ax]:
        # pad_cache lays window layers out as `window` ring slots; the
        # engine guarantees max_len <= window (no wrap), so the leading
        # max_len slots are exactly the live ones
        small = jax.lax.slice_in_dim(small, 0, big.shape[t_ax], axis=t_ax)
    if small.shape[:batch_axis] + small.shape[batch_axis + 1:] != \
            big.shape[:batch_axis] + big.shape[batch_axis + 1:]:
        raise ValueError(f"cache leaf mismatch: {small.shape} vs "
                         f"{big.shape} (batch axis {batch_axis})")
    return jax.lax.dynamic_update_slice_in_dim(
        big, small.astype(big.dtype), slot, axis=batch_axis)
