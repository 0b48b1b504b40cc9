"""Tenant adapter registry: host-side store + fixed-capacity device bank.

The multi-tenant premise (DESIGN.md §2) is that ETHER adapters are O(d)
per linear, so a *device-resident* :class:`~repro.core.peft.AdapterBank`
holding ``capacity`` tenants costs a few KB each — but the tenant
*universe* can be far larger than the bank.  The registry provides the
indirection that makes that work without ever recompiling the serving
functions:

* a host-side store of per-tenant adapter trees (``put`` real finetuned
  adapters, or let ``init_fn`` materialize synthetic ones on demand);
* a fixed-capacity device bank whose leaf shapes NEVER change: tenants
  are onboarded by :meth:`AdapterBank.replace_slot` — a jitted
  functional row swap compiled exactly once;
* tenant→slot mapping with free-list allocation and LRU eviction;
  slots serving in-flight requests are pinned and never evicted.

Unmapped bank rows hold each method's *identity* adapter
(``methods.identity_like`` — zero hyperplanes for the reflection
methods, zero ``b`` factors for DeLoRA, all-ones scales for HyperAdapt),
so even a stray gather of a free slot serves the *base* model rather
than another tenant's weights.

Two-tier serving (DESIGN.md §11): on top of the bank, the registry can
run a fixed-capacity :class:`~repro.core.peft.MergedCache` of fully
*merged* per-tenant weights — the hot tier.  Promotion/demotion is
driven by the request stream (windowed frequency with hysteresis +
minimum dwell so borderline tenants don't thrash merge work, LRU
eviction under capacity pressure, pinned tenants protected in BOTH
tiers).  Promotion runs the kernel-backed ``ether_merge`` /
``etherplus_merge`` ops through one jitted merge compiled exactly once
(``merge_traces``), dispatched asynchronously so in-flight decode never
blocks on a merge: the hot tier only starts serving an entry once its
device buffers report ready.  Hot tenants stay bank-resident too — the
merged tier is a pure fast path, never the only copy.

Replica regions (DESIGN.md §14): with :meth:`configure_regions` the
bank's row range is partitioned into contiguous per-replica regions.  A
tenant may hold copies in several regions (one row each); residency,
pins, free lists and LRU order are tracked per region so one replica's
churn never evicts rows another replica's in-flight requests depend on.
Quarantine and eviction storms span all copies.  The default single
region keeps every existing call site byte-identical in behavior.

Mesh attach (DESIGN.md §14): :meth:`attach_mesh` commits the bank to a
replicated layout on a device mesh and re-pins the jitted swap/merge
output shardings — ETHER rows are O(d), so full bank replication costs
KBs per device and keeps the batched gather-and-reflect collective-free
while tenant churn never changes a jit signature.
"""

from __future__ import annotations

import time
from collections import OrderedDict, deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import methods as _methods
from repro.core.peft import (AdapterBank, MergedCache,
                             _flatten_adapter_modules, init_adapter_bank,
                             init_adapters, merge_params,
                             validate_tenant_ids)
from repro.core.transforms import PEFTConfig
from repro.parallel.context import MeshContext, mesh_context
from repro.serving.persistence import (MethodMismatchError,
                                       StoreCorruptionError)
from repro.serving.scheduler import QuarantineError

Params = dict[str, Any]


class AdapterValidationError(ValueError):
    """A ``put`` adapter tree does not match the bank layout — wrong
    module set, leaf shape/dtype mismatch, or non-finite values.  Raised
    at the host boundary with the offending path named, instead of
    failing later inside jit with an opaque shape-error trace (or, for
    non-finite values, silently poisoning every decode batch the tenant
    joins)."""


class AdapterRegistry:
    """Fixed-capacity device adapter bank with tenant→slot indirection."""

    def __init__(self, params: Params, peft: PEFTConfig, capacity: int, *,
                 n_tenants: Optional[int] = None,
                 rng: Optional[jax.Array] = None,
                 init_fn: Optional[Callable[[int], Params]] = None,
                 merged_capacity: int = 0, promote_after: int = 3,
                 demote_below: int = 1, window: int = 32,
                 min_dwell: int = 16, merge_retries: int = 2,
                 merge_backoff_s: float = 0.0, faults=None,
                 store=None, journal=None):
        if peft.method not in AdapterBank.BANK_METHODS:
            raise ValueError(f"registry serves {AdapterBank.BANK_METHODS} "
                             f"banks only (got {peft.method!r})")
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if merged_capacity < 0:
            raise ValueError("merged_capacity must be >= 0")
        if not 0 <= demote_below < promote_after:
            # hysteresis band: a tenant must cool strictly below
            # demote_below (< promote_after) before its merge is
            # discarded, else oscillation at the boundary would re-merge
            # every swing
            raise ValueError(f"need 0 <= demote_below < promote_after "
                             f"(got {demote_below} / {promote_after})")
        self.capacity = capacity
        self.n_tenants = n_tenants          # universe size; None = open
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._params, self._peft = params, peft
        seed = init_adapter_bank(self._rng, params, peft, 1)
        # free rows hold the method's identity adapter (NOT blanket
        # zeros: HyperAdapt's identity is all-ones scales) so a stray
        # gather of an unmapped slot serves the base model
        ident = _methods.identity_like(peft.method, seed.tree)
        self.bank = AdapterBank(ident, 1, seed.stack_ndims).with_capacity(
            capacity, method=peft.method)
        self._store: dict[int, Params] = {}
        self._init_fn = init_fn or self._default_init(params, peft)
        # -- regioned residency (DESIGN.md §14) ------------------------
        # tid -> {region: slot}; per-region free lists / LRU; pins keyed
        # (region, tid).  One region by default == the historical layout.
        self._n_regions = 1
        self._region_bounds: list[tuple[int, int]] = [(0, capacity)]
        self._slots_of: dict[int, dict[int, int]] = {}
        self._tenant_of: dict[int, int] = {}
        self._lru: list[OrderedDict[int, None]] = [OrderedDict()]
        self._free: list[list[int]] = [list(range(capacity))]
        self._pins: dict[tuple[int, int], int] = {}
        # -- mesh placement (None until attach_mesh) -------------------
        self._mesh = None
        self._replicated = None
        # -- hot tier: merged-weight cache + frequency/LRU policy ------
        self.merged_capacity = merged_capacity
        self.promote_after = promote_after
        self.demote_below = demote_below
        self.window = window
        self.min_dwell = min_dwell
        self.merged = MergedCache.empty(merged_capacity)
        self._mslot_of: dict[int, int] = {}
        self._mfree = list(range(merged_capacity))
        self._mlru: OrderedDict[int, None] = OrderedDict()
        self._mwindow: deque[int] = deque()   # last `window` request tids
        self._mcounts: dict[int, int] = {}    # tid -> count in window
        self._promoted_at: dict[int, int] = {}  # tid -> request ordinal
        self._merge_t0: dict[int, float] = {}   # pending-ready merges
        self._requests_seen = 0
        # -- degradation state (DESIGN.md §12) -------------------------
        if merge_retries < 0:
            raise ValueError("merge_retries must be >= 0")
        self.merge_retries = merge_retries
        self.merge_backoff_s = merge_backoff_s
        self._faults = faults                  # FaultPlan | None
        # -- durability (DESIGN.md §13) --------------------------------
        # `store` is the durable per-tenant AdapterStore (None = the
        # host dict `_store` is the only copy and a process death loses
        # every put); `journal` receives registry membership events so
        # a warm restart rebuilds bank residency + the hot set.
        self.store = store
        self._journal = journal
        if store is not None:
            # pin the durable store to this registry's method: adopted
            # when the store is unpinned, fenced with a typed error when
            # it was opened for a different method (satellite of the
            # per-file manifest check in AdapterStore.get)
            if getattr(store, "method", None) is None:
                store.method = peft.method
            elif store.method != peft.method:
                raise MethodMismatchError(
                    f"adapter store at {store.root!r} serves method "
                    f"{store.method!r}; registry is configured for "
                    f"{peft.method!r}")
        if journal is not None:
            # method stamp: recovery replays this and refuses to rebuild
            # a {peft.method} registry from another method's journal
            journal.append({"t": "meta", "method": peft.method})
        self._faults_corrupted: set[int] = set()
        self._quarantined: set[int] = set()    # suspect tenants (fenced)
        self._merge_fenced: set[int] = set()   # permanent merge failures
        self.stats = dict(hits=0, misses=0, evictions=0, swaps=0,
                          swap_s=0.0, swap_traces=0, init_traces=0,
                          promotions=0, demotions=0, merged_evictions=0,
                          merges_skipped=0, merge_s=0.0, merge_traces=0,
                          quarantines=0, quarantine_evictions=0,
                          merge_failures=0, merge_retries=0,
                          storm_flushes=0)

        self._build_jits()

    def _build_jits(self) -> None:
        """(Re)build the jitted row swap and merge.  Under a mesh the
        output shardings are pinned explicitly — otherwise an eviction's
        zero-scrub or a merge of a new tenant could let GSPMD drift the
        bank/merged layout, and a drifted input sharding is a new jit
        signature for every serving function downstream (a retrace)."""
        swap_out = merge_out = None
        if self._mesh is not None:
            from repro.parallel.sharding import param_specs, to_shardings
            swap_out = self._replicated
            merge_out = to_shardings(
                param_specs(self._params, self._mesh, serve=True),
                self._mesh)

        def registry_swap(bank, tree, slot):
            # traced body: runs only on a jit cache miss, so this count
            # is the compile count (see ServeEngine.jit_cache_misses)
            self.stats["swap_traces"] += 1
            return bank.replace_slot(slot, tree)

        self._swap = (jax.jit(registry_swap) if swap_out is None else
                      jax.jit(registry_swap, out_shardings=swap_out))

        def registry_merge(base, tree):
            # same trace-counting discipline as _swap: adapter trees
            # share shapes across tenants, so every promotion after the
            # first is a jit cache hit — the merge ops are charged once
            # per promotion, the compile once ever
            self.stats["merge_traces"] += 1
            if self._mesh is None:
                return merge_params(base, tree, self._peft)
            # the merge kernels read the mesh (core.execute.supports)
            with mesh_context(MeshContext(self._mesh, seq_shard=False)):
                return merge_params(base, tree, self._peft)

        self._merge = (jax.jit(registry_merge) if merge_out is None else
                       jax.jit(registry_merge, out_shardings=merge_out))

    # -- mesh placement (DESIGN.md §14) --------------------------------

    def attach_mesh(self, mesh, params: Optional[Params] = None) -> None:
        """Commit the bank to ``mesh`` (fully replicated) and pin the
        jitted swap/merge output layouts.  ``params`` — when given — is
        the engine's already-sharded base tree, which the merge path
        must use so a merged tree never mixes mesh-committed kernels
        with dev0-committed untargeted leaves (an "incompatible
        devices" error inside jit).  Call before any residency exists
        (typically right after engine construction, before warmup)."""
        from jax.sharding import NamedSharding, PartitionSpec
        if self._slots_of or self._mslot_of:
            raise RuntimeError("attach_mesh before any tenant is "
                               "onboarded (bank rows would be resharded "
                               "under in-flight requests)")
        self._mesh = mesh
        self._replicated = NamedSharding(mesh, PartitionSpec())
        if params is not None:
            self._params = params
        self.bank = self.bank.to_device(self._replicated)
        self._build_jits()

    def _to_mesh(self, tree: Params) -> Params:
        """Commit a host/dev0 adapter tree to the mesh (replicated) so a
        jitted swap/merge never mixes committed devices; identity when
        no mesh is attached."""
        if self._replicated is None:
            return tree
        return jax.device_put(tree, self._replicated)

    # -- replica regions (DESIGN.md §14) -------------------------------

    def configure_regions(self, n: int) -> None:
        """Partition the bank's row range into ``n`` contiguous regions
        (one per engine replica).  Region sizes differ by at most one
        row.  Must run before any tenant is onboarded — repartitioning
        a live bank would strand rows under in-flight pins."""
        n = int(n)
        if n < 1:
            raise ValueError("need at least one region")
        if n > self.capacity:
            raise ValueError(f"{n} regions need capacity >= {n} "
                             f"(got {self.capacity})")
        if self._slots_of or any(self._pins.values()):
            raise RuntimeError("configure_regions before any tenant is "
                               "onboarded")
        base, rem = divmod(self.capacity, n)
        bounds, start = [], 0
        for r in range(n):
            end = start + base + (1 if r < rem else 0)
            bounds.append((start, end))
            start = end
        self._n_regions = n
        self._region_bounds = bounds
        self._free = [list(range(s, e)) for s, e in bounds]
        self._lru = [OrderedDict() for _ in range(n)]
        self._pins = {}

    @property
    def n_regions(self) -> int:
        return self._n_regions

    @property
    def method(self) -> str:
        """The PEFT method this registry's bank serves."""
        return self._peft.method

    def regions_holding(self, tenant_id: int) -> tuple[int, ...]:
        """Regions currently holding a copy of the tenant's adapters
        (the scheduler's affinity signal for replica placement)."""
        return tuple(sorted(self._slots_of.get(int(tenant_id), {})))

    def _pinned(self, tid: int, region: Optional[int] = None) -> int:
        """In-flight pin count for ``tid`` — in one region, or summed
        over all copies (the tenant-wide guard quarantine and the
        merged tier use: a tenant is only safe to drop when NO replica
        is serving it)."""
        if region is not None:
            return self._pins.get((int(region), tid), 0)
        return sum(c for (_, t), c in self._pins.items() if t == tid)

    def _default_init(self, params, peft):
        """Deterministic per-tenant synthetic adapters: one jitted init
        reused for every tenant id (no per-tenant recompiles)."""
        base = jax.random.fold_in(self._rng, 0x5eed)

        def registry_init(tid):
            self.stats["init_traces"] += 1
            return init_adapters(jax.random.fold_in(base, tid),
                                 params, peft)

        fn = jax.jit(registry_init)
        return lambda tid: fn(jnp.int32(tid))

    def warm_init(self) -> None:
        """Trace the synthetic-init jit without consulting the host
        cache or the durable store.  Post-restart, warmup's
        ``adapters_for(0)`` may be satisfied by an adopted durable copy,
        leaving the init path untraced until the first store-miss tenant
        arrives mid-flight — which would trip the no-retrace contract."""
        jax.block_until_ready(
            jax.tree_util.tree_leaves(self._init_fn(0))[0])

    # -- host-side tenant store --------------------------------------

    def put(self, tenant_id: int, adapters: Params) -> None:
        """Register (or update) a tenant's adapter tree.  If the tenant
        is currently resident its bank row is refreshed in place.

        The tree is validated against the bank layout at this host
        boundary (:meth:`validate_adapters`) — structure, shapes,
        dtypes, finiteness — so a malformed upload raises a typed
        :class:`AdapterValidationError` here instead of failing later
        inside jit (or poisoning decode).  A validated ``put`` is also
        the rehabilitation path: it clears the tenant's quarantine flag
        and merge fence, since both mark the *old* adapters as bad.

        With a durable store attached, the put spills through it FIRST
        (write-then-rename atomic file, DESIGN.md §13) — validation
        precedes the spill, so a rejected put never leaves a file
        behind, and a crash between the durable write and the host-side
        insert below is recoverable: the restarted registry's
        load-on-miss path adopts the newer on-disk version."""
        self.validate(tenant_id)
        self.validate_adapters(adapters)
        tid = int(tenant_id)
        if self.store is not None:
            self.store.put(tid, adapters)
        self._store[tid] = adapters
        if tid in self._quarantined:
            self._quarantined.discard(tid)
            self._jlog("rehab", tid)
        self._merge_fenced.discard(tid)
        for slot in self._slots_of.get(tid, {}).values():
            self._swap_in(slot, adapters)

    def _jlog(self, ev: str, tid: int) -> None:
        """Journal a registry membership event (no-op unjournaled) —
        recovery replays these to rebuild bank residency, the hot set,
        and quarantine flags in LRU order (DESIGN.md §13)."""
        if self._journal is not None:
            self._journal.append({"t": "reg", "ev": ev, "tid": int(tid)})

    def validate_adapters(self, adapters: Params) -> None:
        """Check an adapter tree against the bank layout: exactly the
        targeted modules, each with exactly the bank's leaf keys, each
        leaf with the bank's per-tenant shape and dtype, every value
        finite.  Raises :class:`AdapterValidationError` naming the first
        offending path."""
        expect: dict[str, dict[str, tuple]] = {}
        for mod, adapter in _flatten_adapter_modules(self.bank.tree):
            nd = self.bank.stack_ndims[mod]
            expect[mod] = {
                k: (v.shape[:nd] + v.shape[nd + 1:], v.dtype)
                for k, v in adapter.items()}
        got = dict(_flatten_adapter_modules(adapters))
        if set(got) != set(expect):
            missing = sorted(set(expect) - set(got))
            extra = sorted(set(got) - set(expect))
            raise AdapterValidationError(
                f"adapter tree does not match the bank's targeted "
                f"modules (missing {missing}, unexpected {extra})")
        for mod, want in expect.items():
            adapter = got[mod]
            if set(adapter) != set(want):
                raise AdapterValidationError(
                    f"{mod}: adapter leaves {sorted(adapter)} != bank "
                    f"leaves {sorted(want)}")
            for k, (shape, dtype) in want.items():
                leaf = adapter[k]
                if tuple(np.shape(leaf)) != tuple(shape):
                    raise AdapterValidationError(
                        f"{mod}/{k}: shape {tuple(np.shape(leaf))} != "
                        f"bank per-tenant shape {tuple(shape)}")
                ldt = getattr(leaf, "dtype", None)
                if ldt != dtype:
                    raise AdapterValidationError(
                        f"{mod}/{k}: dtype {ldt} != bank dtype {dtype} "
                        f"(cast on the client — the bank swap would "
                        f"silently coerce)")
                if not np.all(np.isfinite(np.asarray(leaf))):
                    raise AdapterValidationError(
                        f"{mod}/{k}: non-finite values (NaN/Inf) — a "
                        f"poisoned adapter would corrupt every decode "
                        f"batch its tenant joins")

    def adapters_for(self, tenant_id: int) -> Params:
        tid = int(tenant_id)
        if tid not in self._store:
            durable = self._load_durable(tid)
            self._store[tid] = (durable if durable is not None
                                else self._init_fn(tid))
        if self._faults is not None and tid not in self._faults_corrupted:
            # injection site for the 'corrupt' fault class: poison the
            # stored tree BELOW the put-validation boundary (modeling
            # corruption the host validator cannot see), exactly once
            # per plan per tenant
            self._faults_corrupted.add(tid)
            kind = self._faults.corrupt_kind(tid)
            if kind is not None:
                from repro.serving.faults import corrupt_tree
                self._store[tid] = corrupt_tree(self._store[tid], kind)
        return self._store[tid]

    def _load_durable(self, tid: int) -> Optional[Params]:
        """Load-on-miss from the durable store; None when the tenant
        has no durable copy (synthetic init takes over).  The loaded
        tree re-runs :meth:`validate_adapters` — on-disk corruption
        (checksum failure OR a tree that validates structurally but
        fails the bank layout) lands in the SAME typed-quarantine path
        as live poisoning instead of crashing restore (DESIGN.md §13)."""
        if self.store is None:
            return None
        try:
            tree = self.store.get(tid)
        except StoreCorruptionError as e:
            self._quarantine_durable(tid, e)
        if tree is None:
            return None
        try:
            self.validate_adapters(tree)
        except AdapterValidationError as e:
            self._quarantine_durable(tid, e)
        return tree

    def _quarantine_durable(self, tid: int, err: Exception) -> None:
        """A tenant's durable copy is poisoned: drop it (a restart must
        not resurrect it), quarantine the tenant, and refuse the load
        with the typed error the scheduler accounts as
        ``failed_quarantine``."""
        self.store.delete(tid)
        self.mark_suspect(tid)
        raise QuarantineError(
            f"tenant {tid} durable adapters failed validation on "
            f"restore: {err}") from err

    # -- slot lifecycle ----------------------------------------------

    def validate(self, tenant_id) -> None:
        """Frontend guard: ids must be integers in the tenant universe
        (see :func:`repro.core.peft.validate_tenant_ids` for why a bad
        id must raise here instead of clamping inside a gather)."""
        bound = self.n_tenants if self.n_tenants is not None else (
            int(tenant_id) + 1 if np.ndim(tenant_id) == 0
            else int(np.max(np.asarray(tenant_id))) + 1)
        validate_tenant_ids(tenant_id, bound)

    def can_acquire(self, tenant_id: int,
                    region: Optional[int] = None) -> bool:
        """True iff :meth:`acquire` would succeed right now — the
        tenant is resident, or a bank slot is free/evictable.  With
        ``region`` the check is scoped to that replica's row range;
        None asks "any region at all" (the scheduler uses this as
        back-pressure: when every resident tenant is pinned by
        in-flight requests, new distinct tenants wait in the queue
        instead of crashing the replay)."""
        tid = int(tenant_id)
        copies = self._slots_of.get(tid, {})
        regions = (range(self._n_regions) if region is None
                   else (int(region),))
        for r in regions:
            if r in copies or self._free[r]:
                return True
            if any(self._pins.get((r, t), 0) == 0 for t in self._lru[r]):
                return True
        return False

    def acquire(self, tenant_id: int, region: int = 0) -> int:
        """Pin ``tenant_id`` into ``region``'s row range; returns its
        slot id.

        Cache hit (a copy already in that region): bump LRU recency.
        Miss: take a free row there, else evict the region's
        least-recently-used *unpinned* tenant; swap the tenant's
        adapters into that row (one jitted functional row update — leaf
        shapes never change, so nothing retraces).

        Traced as the span ``registry.acquire`` (stats ``tenant``,
        ``hit``) with, on a miss, a child ``registry.swap`` (stat
        ``slot``) from materializing the adapters to the swapped bank
        being ready."""
        with jax.profiler.TraceAnnotation(
                "registry.acquire", tenant=tenant_id) as span:
            return self._acquire(tenant_id, region, span)

    def _acquire(self, tenant_id: int, region: int, span) -> int:
        self.validate(tenant_id)
        tid, r = int(tenant_id), int(region)
        if tid in self._quarantined:
            # backstop behind the scheduler's is_quarantined shed: a
            # poisoned adapter must never re-enter the batch
            raise QuarantineError(f"tenant {tid} is quarantined")
        slot = self._slots_of.get(tid, {}).get(r)
        span.set_metadata(hit=int(slot is not None))
        if slot is not None:
            self.stats["hits"] += 1
        else:
            self.stats["misses"] += 1
            with jax.profiler.TraceAnnotation("registry.swap") as swap:
                # materialize BEFORE taking a slot: a durable-load
                # failure (QuarantineError) must leave the slot maps
                # untouched
                tree = self.adapters_for(tid)
                slot = self._take_slot(r)
                swap.set_metadata(slot=slot)
                first_copy = tid not in self._slots_of
                self._slots_of.setdefault(tid, {})[r] = slot
                self._tenant_of[slot] = tid
                self._swap_in(slot, tree)
            if first_copy:
                self._jlog("onboard", tid)
        self._lru[r][tid] = None
        self._lru[r].move_to_end(tid)
        self._pins[(r, tid)] = self._pins.get((r, tid), 0) + 1
        self._note_request(tid)
        return slot

    def release(self, tenant_id: int, region: int = 0) -> None:
        """Unpin one in-flight request; the tenant stays resident (warm)
        until LRU eviction needs its slot.  A quarantined tenant's
        deferred eviction (pins are respected — sibling in-flight
        requests of the same tenant finish or are failed by their own
        detection, never yanked by an eviction) runs when the last pin
        across ALL regions drops."""
        tid, r = int(tenant_id), int(region)
        n = self._pins.get((r, tid), 0)
        if n <= 0:
            raise ValueError(f"tenant {tid} released but not acquired")
        self._pins[(r, tid)] = n - 1
        if (tid in self._quarantined and self._pinned(tid) == 0):
            self._evict_quarantined(tid)

    # -- quarantine & storms (DESIGN.md §12) ---------------------------

    def is_quarantined(self, tenant_id: int) -> bool:
        return int(tenant_id) in self._quarantined

    def mark_suspect(self, tenant_id: int) -> None:
        """Quarantine a tenant whose adapters produced non-finite
        logits: fence it from (re-)acquisition and evict it from both
        tiers — immediately if unpinned, else deferred to the last
        :meth:`release`.  Rehabilitation is a fresh validated
        :meth:`put`."""
        tid = int(tenant_id)
        if tid in self._quarantined:
            return
        self._quarantined.add(tid)
        self.stats["quarantines"] += 1
        self._jlog("quarantine", tid)
        if self._pinned(tid) == 0:
            self._evict_quarantined(tid)

    def _evict_quarantined(self, tid: int) -> None:
        """Remove a quarantined tenant from both tiers — every regional
        copy — and scrub its bank rows back to the method's identity
        adapter.  Scrubbing — not mere freeing — because an identity row
        is harmless under any gather, while a NaN row is the one kind of
        stale data masked arithmetic cannot neutralize
        (``0 * NaN = NaN``).  The poisoned host copy is dropped too."""
        if tid in self._mslot_of:
            self.demote(tid)
        for r, slot in self._slots_of.pop(tid, {}).items():
            del self._tenant_of[slot]
            self._lru[r].pop(tid, None)
            self._pins.pop((r, tid), None)
            ident = _methods.identity_like(self._peft.method,
                                           self.bank.select(slot))
            self._swap_in(slot, ident)
            self._free[r].append(slot)
        self._store.pop(tid, None)
        if self.store is not None:
            # the durable copy is the same poisoned tree — a restart
            # must not resurrect it (rehabilitation is a fresh put)
            self.store.delete(tid)
        self.stats["quarantine_evictions"] += 1

    def flush_unpinned(self) -> int:
        """Eviction storm (memory-pressure mass eviction): drop every
        *unpinned* tenant from both tiers; returns how many entries were
        flushed.  Pinned tenants (in-flight requests) keep both their
        bank row and any merged entry — serving survives the storm and
        re-onboards the flushed tenants on demand through the ordinary
        swap/merge paths (no retraces: shapes never changed)."""
        n = 0
        for tid in [t for t in self._mslot_of if self._pinned(t) == 0]:
            self.demote(tid)
            n += 1
        for r in range(self._n_regions):
            for tid in [t for t in self._lru[r]
                        if self._pins.get((r, t), 0) == 0]:
                self._drop_copy(tid, r)
                self.stats["evictions"] += 1
                n += 1
        self.stats["storm_flushes"] += 1
        return n

    def _drop_copy(self, tid: int, r: int) -> None:
        """Remove the tenant's copy in region ``r`` (row back to the
        region's free list).  Journals ``evict`` only when the LAST
        copy disappears — the journal records membership, not
        placement, and replay rebuilds placement round-robin."""
        slot = self._slots_of[tid].pop(r)
        if not self._slots_of[tid]:
            del self._slots_of[tid]
            self._jlog("evict", tid)
        del self._tenant_of[slot]
        del self._lru[r][tid]
        self._pins.pop((r, tid), None)
        self._free[r].append(slot)

    def _take_slot(self, region: int = 0) -> int:
        r = int(region)
        if self._free[r]:
            return self._free[r].pop()
        for tid in self._lru[r]:                   # least recent first
            if self._pins.get((r, tid), 0) == 0:
                self._drop_copy(tid, r)
                self.stats["evictions"] += 1
                return self._free[r].pop()
        raise RuntimeError(f"all {self.capacity} resident tenants are "
                           f"pinned by in-flight requests")

    def _swap_in(self, slot: int, adapters: Params) -> None:
        t0 = time.perf_counter()
        self.bank = self._swap(self.bank, self._to_mesh(adapters),
                               jnp.int32(slot))
        jax.block_until_ready(jax.tree_util.tree_leaves(self.bank.tree)[0])
        self.stats["swaps"] += 1
        self.stats["swap_s"] += time.perf_counter() - t0

    # -- hot tier: merge-on-promotion ---------------------------------

    def _note_request(self, tid: int) -> None:
        """Advance the windowed-frequency policy by one admitted request
        and apply promotions/demotions.  Host-side bookkeeping only —
        the merge itself is dispatched asynchronously, so this never
        blocks in-flight decode."""
        self._requests_seen += 1
        if self.merged_capacity == 0:
            return
        self._mwindow.append(tid)
        self._mcounts[tid] = self._mcounts.get(tid, 0) + 1
        if len(self._mwindow) > self.window:
            old = self._mwindow.popleft()
            left = self._mcounts.get(old, 1) - 1
            if left:
                self._mcounts[old] = left
            else:
                self._mcounts.pop(old, None)
        if (tid not in self._mslot_of
                and tid not in self._merge_fenced
                and self._mcounts[tid] >= self.promote_after):
            self.promote(tid)
        for t in [t for t in self._mslot_of
                  if self._mcounts.get(t, 0) < self.demote_below]:
            # hysteresis: only demote after the tenant has been merged
            # for min_dwell requests AND cooled strictly below the lower
            # threshold; pinned tenants (in-flight requests) never lose
            # their merged entry mid-request
            if (self._requests_seen - self._promoted_at[t] >= self.min_dwell
                    and self._pinned(t) == 0):
                self.demote(t)

    def promote(self, tenant_id: int) -> bool:
        """Merge ``tenant_id``'s reflection into a full weight tree and
        install it in the hot tier.  Returns False (and counts
        ``merges_skipped``) when every merged entry is pinned — a
        promotion must never abort serving.  The merge runs through the
        kernel-backed ``*_merge`` ops inside one jitted function
        (compiled once — ``merge_traces``) and is NOT blocked on: the
        entry starts serving once its buffers report ready
        (:meth:`merged_for`).

        A merge dispatch that raises is retried up to ``merge_retries``
        times with exponential backoff (``merge_backoff_s`` base); when
        retries are exhausted the tenant is *fenced* to the bank tier —
        it keeps serving un-merged and is never re-promoted
        (``merge_failures``) until a fresh :meth:`put` replaces the
        adapters the merge choked on."""
        tid = int(tenant_id)
        if self.merged_capacity == 0:
            raise ValueError("registry has no merged tier "
                             "(merged_capacity=0)")
        if tid in self._mslot_of:
            return True
        if tid in self._merge_fenced:
            self.stats["merges_skipped"] += 1
            return False
        if self._mfree:
            mslot = self._mfree.pop()
        else:
            mslot = self._evict_merged()
            if mslot is None:
                self.stats["merges_skipped"] += 1
                return False
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("registry.merge", tenant=tid):
            tree = self._dispatch_merge(tid)
        self.stats["merge_s"] += time.perf_counter() - t0
        if tree is None:
            # retries exhausted: return the slot, fence the tenant to
            # the bank tier — a promotion must never abort serving
            self._mfree.append(mslot)
            self._merge_fenced.add(tid)
            self.stats["merge_failures"] += 1
            return False
        self.merged = self.merged.put(mslot, tree)
        self.stats["promotions"] += 1
        self._mslot_of[tid] = mslot
        self._mlru[tid] = None
        self._mlru.move_to_end(tid)
        self._promoted_at[tid] = self._requests_seen
        self._merge_t0[tid] = t0
        self._jlog("promote", tid)
        return True

    def demote(self, tenant_id: int) -> None:
        """Drop a tenant's merged entry (the tenant keeps serving from
        the bank tier).  Dropping releases the only strong references to
        the merged kernels, freeing their device memory."""
        tid = int(tenant_id)
        mslot = self._mslot_of.pop(tid)
        self.merged = self.merged.drop(mslot)
        self._mfree.append(mslot)
        self._mlru.pop(tid, None)
        self._promoted_at.pop(tid, None)
        self._merge_t0.pop(tid, None)
        self.stats["demotions"] += 1
        self._jlog("demote", tid)

    def _evict_merged(self) -> Optional[int]:
        """Free the least-recently-*served* unpinned merged entry; None
        when every merged tenant is pinned by in-flight requests."""
        for tid in self._mlru:                     # least recent first
            if self._pinned(tid) == 0:
                mslot = self._mslot_of.pop(tid)
                self.merged = self.merged.drop(mslot)
                del self._mlru[tid]
                self._promoted_at.pop(tid, None)
                self._merge_t0.pop(tid, None)
                self.stats["merged_evictions"] += 1
                self._jlog("demote", tid)
                return mslot
        return None

    def _dispatch_merge(self, tid: int) -> Optional[Params]:
        """Bounded retry-with-backoff around the jitted merge dispatch;
        None when every attempt failed.  Only ``RuntimeError`` is
        retried (XLA runtime failures and :class:`InjectedFault` both
        surface as RuntimeError) — anything else is a registry bug and
        propagates."""
        if self._faults is not None:
            # mid-merge crash boundary (DESIGN.md §13): SimulatedCrash
            # is a BaseException, so the RuntimeError retry below can
            # NOT absorb it — a process death is not a merge failure
            self._faults.crash_now("merge")
        for attempt in range(1 + self.merge_retries):
            if attempt:
                self.stats["merge_retries"] += 1
                if self.merge_backoff_s:
                    time.sleep(self.merge_backoff_s * 2 ** (attempt - 1))
            try:
                if (self._faults is not None
                        and self._faults.merge_should_fail(tid)):
                    from repro.serving.faults import InjectedFault
                    raise InjectedFault(
                        f"injected merge failure for tenant {tid}")
                return self.merge_tree(tid)
            except RuntimeError:
                continue
        return None

    def merge_tree(self, tenant_id: int) -> Params:
        """The tenant's fully-merged weight tree via the jitted
        kernel-backed merge (deterministic: the tier-faithful oracle
        recomputes the exact tree the engine served)."""
        return self._merge(self._params,
                           self._to_mesh(self.adapters_for(int(tenant_id))))

    def merged_for(self, tenant_id: int) -> Optional[Params]:
        """The tenant's merged tree iff it is hot AND its (async) merge
        has completed — while the merge is still materializing the
        caller keeps serving from the bank, so promotion never stalls
        decode.  Serving an entry bumps its LRU recency."""
        tid = int(tenant_id)
        mslot = self._mslot_of.get(tid)
        if mslot is None:
            return None
        tree = self.merged.get(mslot)
        if tid in self._merge_t0:
            leaves = jax.tree_util.tree_leaves(tree)
            if not all(getattr(l, "is_ready", lambda: True)()
                       for l in leaves):
                return None
            del self._merge_t0[tid]
        self._mlru.move_to_end(tid)
        return tree

    def is_merged(self, tenant_id: int) -> bool:
        return int(tenant_id) in self._mslot_of

    def warm_swap(self) -> None:
        """Compile the jitted row swap on tenant 0's tree (and throw
        the result away) so the first real onboard after warmup is a
        jit cache hit.  Routes through :meth:`_to_mesh` like every live
        swap, so the compiled signature matches production exactly."""
        tree = self.adapters_for(0)
        discard = self._swap(self.bank, self._to_mesh(tree), jnp.int32(0))
        jax.block_until_ready(jax.tree_util.tree_leaves(discard.tree)[0])

    def warm_scrub(self) -> None:
        """Compile the eager ops of the quarantine scrub (a bank row read
        back, then its identity tree) so the first quarantine after
        warmup compiles nothing."""
        ident = _methods.identity_like(self._peft.method,
                                       self.bank.select(0))
        jax.block_until_ready(jax.tree_util.tree_leaves(ident)[0])

    def warm_merge(self) -> None:
        """Compile the jitted merge on a throwaway tree so the first
        real promotion is a jit cache hit (``jit_cache_misses`` stays
        flat across promotions mid-trace)."""
        if self.merged_capacity == 0:
            return
        discard = self.merge_tree(0)
        jax.block_until_ready(jax.tree_util.tree_leaves(discard)[0])

    # -- warm restart (DESIGN.md §13) ---------------------------------

    def restore_membership(self, resident=(), merged=(),
                           quarantined=()) -> dict[str, int]:
        """Rebuild cache membership after a process death, from the
        journal's replayed registry events: ``resident`` / ``merged``
        in LRU order (least recent first), ``quarantined`` as a set.

        Quarantine flags are restored FIRST (a poisoned tenant must not
        be re-onboarded), then bank rows are re-onboarded through the
        ordinary load-or-init path — so durable copies are adopted and
        a corrupt durable copy lands in the typed-quarantine path
        (counted ``corrupt``, restore continues) — then hot tenants are
        re-merged via the ordinary :meth:`promote`.  Call before the
        engine's warmup: the swaps/merges here prime the same jitted
        functions, and traffic after warmup stays retrace-free."""
        out = dict(resident=0, merged=0, quarantined=0, corrupt=0,
                   skipped=0)
        for tid in quarantined:
            tid = int(tid)
            if tid not in self._quarantined:
                self._quarantined.add(tid)
                self.stats["quarantines"] += 1
                self._jlog("quarantine", tid)
            out["quarantined"] += 1
        rr = 0
        for tid in resident:
            tid = int(tid)
            if tid in self._quarantined or tid in self._slots_of:
                out["skipped"] += 1
                continue
            # round-robin restored tenants over regions with free rows
            # (the journal records membership, not placement); when no
            # region has a free row, capacity shrank across the restart:
            # keep the most recent tenants (the list is LRU-ordered, so
            # earlier entries are the right ones to lose)
            r = next((x % self._n_regions
                      for x in range(rr, rr + self._n_regions)
                      if self._free[x % self._n_regions]), None)
            if r is None:
                out["skipped"] += 1
                continue
            rr = r + 1
            try:
                tree = self.adapters_for(tid)
            except QuarantineError:
                out["corrupt"] += 1
                continue
            slot = self._take_slot(r)
            self._slots_of[tid] = {r: slot}
            self._tenant_of[slot] = tid
            self._swap_in(slot, tree)
            self._lru[r][tid] = None
            self._lru[r].move_to_end(tid)
            self._jlog("onboard", tid)
            out["resident"] += 1
        if self.merged_capacity:
            for tid in merged:
                tid = int(tid)
                if tid in self._quarantined or tid in self._merge_fenced:
                    out["skipped"] += 1
                    continue
                try:
                    out["merged"] += int(self.promote(tid))
                except QuarantineError:
                    out["corrupt"] += 1
        return out

    # -- introspection ------------------------------------------------

    def quarantined(self) -> frozenset:
        """Tenant ids currently fenced by quarantine."""
        return frozenset(self._quarantined)

    def merge_fenced(self) -> frozenset:
        """Tenant ids fenced from re-promotion by permanent merge
        failure (bank-tier only until a fresh ``put``)."""
        return frozenset(self._merge_fenced)

    def merged_resident(self) -> dict[int, int]:
        """tenant id → merged slot for every hot-tier tenant."""
        return dict(self._mslot_of)

    def merged_size_bytes(self) -> int:
        """HBM held by the hot tier (targeted kernels only — untargeted
        leaves are shared with the base params, not copied)."""
        return self.merged.size_bytes(self._params)

    def resident(self) -> dict[int, int]:
        """tenant id → slot for every loaded tenant (the lowest-slot
        copy when a tenant is resident in several regions)."""
        return {tid: min(copies.values())
                for tid, copies in self._slots_of.items()}

    def slot_tenant(self, slot: int) -> Optional[int]:
        return self._tenant_of.get(slot)

    @property
    def n_free(self) -> int:
        return sum(len(f) for f in self._free)
