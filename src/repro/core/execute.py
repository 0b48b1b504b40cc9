"""Execution-backend dispatch for the ETHER hot paths (DESIGN.md §3).

``core.transforms.adapted_dense`` (and ``merge_weight``) route every
ETHER compute through this registry instead of hard-coding jnp einsums.
The registry maps ``(op, backend)`` to an implementation:

``jnp``
    The reference einsum formulations in ``core.transforms`` — always
    available, always correct, differentiable; the default backend.

``pallas``
    The TPU kernels in ``repro.kernels`` (``ether_reflect``,
    ``householder_gemm``, ``ether_merge``, ``ether_reflect_batched``,
    and the fused ETHER+/multi-tenant tier: ``etherplus_gemm``,
    ``householder_gemm_batched``, ``etherplus_reflect_batched``,
    ``etherplus_merge``).  Off-TPU the kernels run in interpret mode
    (Python emulation) so the identical code path is validated on CPU
    and deployed on TPU.

``auto``
    Per-call selection: ``pallas`` when the operand shapes satisfy the
    kernel's tiling constraints (see the ``supports_rule`` predicates),
    ``jnp`` otherwise.  This is what serving configs use — hot prefill
    shapes hit the MXU kernels, odd decode shapes fall back.

Selection happens at trace time (shapes are static under jit), so a
jitted forward bakes in exactly one implementation per call site and the
dispatch itself costs nothing at runtime.  ``counters()`` exposes how
often each (op, backend) pair was *traced* — tests and the serving
driver use it to assert the Pallas path is actually live.

The shared ``_interpret`` helper lives here (moved from ``kernels.ops``)
so direct kernel callers and the dispatch layer agree on one platform
auto-detection rule.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import jax

BACKENDS = ("jnp", "pallas", "auto")

_REGISTRY: dict[tuple[str, str], Callable[..., Any]] = {}
_SUPPORTS: dict[str, Callable[..., bool]] = {}
_COUNTERS: dict[str, int] = {}


def _interpret(flag: bool | None = None) -> bool:
    """Pallas interpret-mode policy: explicit flag wins, else emulate
    whenever we are not actually on a TPU."""
    if flag is not None:
        return bool(flag)
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def register(op: str, backend: str):
    """Decorator: register ``fn`` as the ``backend`` implementation of
    ``op``."""
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"implementations must be 'jnp' or 'pallas', "
                         f"got {backend!r}")

    def deco(fn):
        _REGISTRY[(op, backend)] = fn
        return fn
    return deco


def supports_rule(op: str):
    """Decorator: register the shape-tileability predicate consulted by
    the ``auto`` backend before selecting the Pallas implementation."""
    def deco(fn):
        _SUPPORTS[op] = fn
        return fn
    return deco


def available(op: str) -> tuple[str, ...]:
    """Backends registered for ``op`` (registry introspection)."""
    return tuple(b for (o, b) in _REGISTRY if o == op)


def supports(op: str, *args, **kwargs) -> bool:
    """True when the Pallas kernel's tiling constraints accept these
    operand shapes, and the program being traced can hold the kernel."""
    rule = _SUPPORTS.get(op)
    if rule is None or _spmd_on_tpu():
        return False
    return bool(rule(*args, **kwargs))


def _spmd_on_tpu() -> bool:
    """True while tracing under a multi-device mesh on a TPU.  GSPMD
    cannot partition a Mosaic kernel (it would need a shard_map), so
    the sharded serve engine (DESIGN.md §14) runs every adapter op on
    its jnp path there; interpret-mode kernels are plain HLO and
    partition like any other op."""
    from repro.parallel.context import get_context
    ctx = get_context()
    return (ctx is not None and ctx.mesh.size > 1
            and jax.default_backend() == "tpu")


def selected_backend(op: str, backend: str, *args, **kwargs) -> str:
    """Resolve ``auto`` to a concrete backend for these operands."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    if backend != "auto":
        return backend
    if ("pallas" in available(op)) and supports(op, *args, **kwargs):
        return "pallas"
    return "jnp"


def dispatch(op: str, backend: str, *args, **kwargs):
    """Execute ``op`` on the resolved backend, recording a trace count.

    Counter keys are truthful about what actually runs: an explicit
    ``backend='pallas'`` on shapes the kernel's tiling rejects still
    calls the pallas wrapper (which safely falls back to the jnp ref
    internally) but is counted as ``op.pallas_fallback``, so "the Pallas
    path is live" can be asserted from counters alone."""
    be = selected_backend(op, backend, *args, **kwargs)
    impl = _REGISTRY.get((op, be))
    if impl is None:
        raise KeyError(f"no {be!r} implementation registered for {op!r}")
    key = f"{op}.{be}"
    if be == "pallas" and not supports(op, *args, **kwargs):
        key = f"{op}.pallas_fallback"
    _COUNTERS[key] = _COUNTERS.get(key, 0) + 1
    return impl(*args, **kwargs)


def is_bwd_op(op: str) -> bool:
    """True for registered backward ops (the ``*_bwd`` tier)."""
    return op.endswith("_bwd")


def counters(phase: str | None = None) -> dict[str, int]:
    """Snapshot of per-(op, backend) trace counts.

    ``phase='fwd'`` returns only forward-op keys, ``phase='bwd'`` only
    the ``*_bwd`` dispatches — so tests can assert the backward actually
    ran on Pallas (a silent ref-AD fallback shows up as ``*_bwd.jnp``)."""
    if phase is None:
        return dict(_COUNTERS)
    if phase not in ("fwd", "bwd"):
        raise ValueError(f"phase must be 'fwd', 'bwd' or None, got "
                         f"{phase!r}")
    want = phase == "bwd"
    return {k: v for k, v in _COUNTERS.items()
            if is_bwd_op(k.split(".", 1)[0]) == want}


def reset_counters() -> None:
    _COUNTERS.clear()


# ---------------------------------------------------------------------------
# Tileability predicates — mirror the fallback logic in kernels.ops so
# `auto` selects pallas exactly when the wrapper would not itself fall
# back to the jnp reference.
#
# The TPU's (8, 128) block tiling is required only on a real TPU
# (`lane_ok`): interpret mode (the only Pallas execution path on CPU/GPU)
# has no tiling, so `auto` keeps smoke configs (d_model=64/96) on the
# kernel path there.  tests/test_tpu_compile.py compiles the kernels for
# a v5e at real widths, which is what keeps these rules true on a chip.
# ---------------------------------------------------------------------------

def lane_ok(dim: int, align: int = 128) -> bool:
    """Block-dim tiling constraint: ``align``-aligned on a real TPU (128
    lanes for a block's last dim, 8 sublanes for its second-to-last);
    interpret mode (off-TPU emulation) has no tiling."""
    return dim % align == 0 or jax.default_backend() != "tpu"


def largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= cap — the shared block-shrink
    rule the kernel wrappers use so odd shapes get more, smaller tiles
    instead of crashing."""
    b = min(cap, n)
    while n % b:
        b -= 1
    return b


def gemm_tiles(t: int, d: int, f: int, db: int,
               db_out: int | None = None) -> tuple[int, int, int]:
    """(block_m, block_f, block_k) for the fused reflect-GEMM kernels;
    any zero means the shapes don't tile and callers must fall back.

    ``db_out`` (two-sided ETHER+ only) adds the fused-epilogue
    constraint block_f % db_out == 0: each F-tile must hold whole
    *output* reflection blocks so the epilogue's blockwise projection is
    tile-local.  On a real TPU the minor dims (block_k for the x tile,
    block_f for the w/out tiles) must be 128-lane aligned; interpret
    mode has no lane constraint.  Small row tiles (S=1 decode) are fine
    everywhere — sublanes pad."""
    bm = 128 if t % 128 == 0 else (t if 0 < t <= 256 else 0)
    if f % 128 == 0 and (db_out is None or 128 % db_out == 0):
        bf = 128
    elif 0 < f <= 512 and lane_ok(f):
        bf = f                      # whole rows: db_out | f always holds
    else:
        bf = 0
    bk = db * max(1, min(512, d) // db)
    if d % bk or not lane_ok(bk):
        bk = 0
    return bm, bf, bk


@supports_rule("ether_reflect")
def _sup_reflect(x, u) -> bool:
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    bt = min(256, t)
    return bt > 0 and t % bt == 0


@supports_rule("householder_gemm")
def _sup_hh_gemm(x, w, u) -> bool:
    d, f = w.shape
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    n, db = u.shape
    return n * db == d and all(gemm_tiles(t, d, f, db))


@supports_rule("ether_merge")
def _sup_merge(w, u) -> bool:
    # (db, Tf) tiles of W with the block's vector as a (db, 1) column
    n, db = u.shape
    return lane_ok(w.shape[-1]) and (n == 1 or lane_ok(db, 8))


@supports_rule("ether_reflect_batched")
def _sup_reflect_batched(x, u_bank, ids) -> bool:
    if x.ndim != 3:
        return False
    _, s, d = x.shape
    _, n, db = u_bank.shape
    bs = min(128, s)
    return bs > 0 and s % bs == 0 and lane_ok(d) and n * db == d


@supports_rule("etherplus_gemm")
def _sup_ep_gemm(x, w, u1, v1, u2=None, v2=None) -> bool:
    d, f = w.shape
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    n, db = u1.shape
    if n * db != d:
        return False
    db_out = u2.shape[1] if u2 is not None else None
    bm, bf, bk = gemm_tiles(t, d, f, db, db_out)
    return bool(bm and bf and bk)


@supports_rule("householder_gemm_batched")
def _sup_hh_gemm_batched(x, w, u_bank, ids) -> bool:
    if x.ndim != 3:
        return False
    _, s, d = x.shape
    _, f = w.shape
    _, n, db = u_bank.shape
    if n * db != d:
        return False
    bs, bf, bk = gemm_tiles(s, d, f, db)
    return bool(bs and bf and bk)


@supports_rule("etherplus_reflect_batched")
def _sup_ep_reflect_batched(x, u_bank, v_bank, ids) -> bool:
    if x.ndim != 3:
        return False
    _, s, d = x.shape
    _, n, db = u_bank.shape
    bs = min(128, s)
    return (bs > 0 and s % bs == 0 and n * db == d
            and u_bank.shape == v_bank.shape and lane_ok(d))


@supports_rule("etherplus_merge")
def _sup_ep_merge(w, u1, v1, u2=None, v2=None) -> bool:
    d, f = w.shape
    n, db = u1.shape
    if n * db != d or u1.shape != v1.shape:
        return False
    right_ok = u2 is None or (u2.shape == v2.shape
                              and u2.shape[0] * u2.shape[1] == f)
    return lane_ok(f) and (n == 1 or lane_ok(db, 8)) and right_ok


# ---------------------------------------------------------------------------
# Implementations.  jnp impls import from core.transforms and pallas
# impls from kernels.ops *inside* the function bodies — both modules
# import this one at module scope, so top-level imports would cycle.
#
# Pallas forwards carry a custom_vjp whose backward is itself dispatched
# through this registry: every forward op has a first-class ``<op>_bwd``
# registered with a hand-derived Pallas kernel (pallas backend) and
# ref-AD — XLA differentiating the jnp einsum form — as the jnp backend.
# ``auto`` resolution picks the kernel whenever its tiling supports the
# operand shapes, so jax.grad of a training step runs Pallas in BOTH
# directions; pallas_call itself has no autodiff on the jax versions we
# support, which is why the backwards are hand-derived (DESIGN.md §3).
# ---------------------------------------------------------------------------

def _registry_vjp(op, fn):
    """Wrap a pallas forward with a registry-dispatched backward.

    The backward dispatch is traced like any other op, so counters
    record whether training actually hit the ``<op>_bwd`` kernel
    (``<op>_bwd.pallas``) or fell back to ref-AD (``<op>_bwd.jnp``)."""
    @functools.wraps(fn)
    @jax.custom_vjp
    def wrapped(*args):
        return fn(*args)

    def fwd(*args):
        # Residuals are the primal operands themselves: the backwards
        # recompute normalized directions (O(d), trivial) and — for the
        # two-sided fused GEMM — the pre-epilogue intermediate, instead
        # of saving forward intermediates to HBM.
        return fn(*args), args

    def bwd(residual_args, g):
        return tuple(dispatch(op + "_bwd", "auto", *residual_args, g))

    wrapped.defvjp(fwd, bwd)
    return wrapped


def _ad_bwd(fwd_fn):
    """The jnp backend of a ``*_bwd`` op: XLA AD of the jnp forward."""
    @functools.wraps(fwd_fn)
    def bwd(*args):
        *primals, g = args
        return jax.vjp(fwd_fn, *primals)[1](g)
    return bwd


@register("ether_reflect", "jnp")
def _reflect_jnp(x, u):
    from repro.core.transforms import reflect_activation
    return reflect_activation(x, u)


def _reflect_pallas(x, u):
    from repro.kernels import ops
    return ops.ether_reflect(x, u)


register("ether_reflect", "pallas")(
    _registry_vjp("ether_reflect", _reflect_pallas))


@register("householder_gemm", "jnp")
def _hh_gemm_jnp(x, w, u):
    from repro.core.transforms import reflect_activation
    return reflect_activation(x, u) @ w.astype(x.dtype)


def _hh_gemm_pallas(x, w, u):
    from repro.kernels import ops
    return ops.householder_gemm(x, w, u)


register("householder_gemm", "pallas")(
    _registry_vjp("householder_gemm", _hh_gemm_pallas))


@register("ether_merge", "jnp")
def _merge_jnp(w, u):
    from repro.core.transforms import reflect_weight
    return reflect_weight(w, u)


def _merge_pallas(w, u):
    from repro.kernels import ops
    return ops.ether_merge(w, u)


register("ether_merge", "pallas")(
    _registry_vjp("ether_merge", _merge_pallas))


@register("ether_reflect_batched", "jnp")
def _reflect_batched_jnp(x, u_bank, ids):
    from repro.core.transforms import reflect_activation_batched
    return reflect_activation_batched(x, u_bank, ids)


def _reflect_batched_pallas(x, u_bank, ids):
    from repro.kernels import ops
    return ops.ether_reflect_batched(x, u_bank, ids)


register("ether_reflect_batched", "pallas")(
    _registry_vjp("ether_reflect_batched", _reflect_batched_pallas))


@register("etherplus_gemm", "jnp")
def _ep_gemm_jnp(x, w, u1, v1, u2=None, v2=None):
    from repro.core.transforms import etherplus_activation
    y = etherplus_activation(x, u1, v1) @ w.astype(x.dtype)
    if u2 is not None:
        y = etherplus_activation(y, u2, v2)
    return y


def _ep_gemm_pallas(x, w, u1, v1, u2=None, v2=None):
    from repro.kernels import ops
    return ops.etherplus_gemm(x, w, u1, v1, u2, v2)


register("etherplus_gemm", "pallas")(
    _registry_vjp("etherplus_gemm", _ep_gemm_pallas))


@register("householder_gemm_batched", "jnp")
def _hh_gemm_batched_jnp(x, w, u_bank, ids):
    from repro.core.transforms import reflect_activation_batched
    return reflect_activation_batched(x, u_bank, ids) @ w.astype(x.dtype)


def _hh_gemm_batched_pallas(x, w, u_bank, ids):
    from repro.kernels import ops
    return ops.householder_gemm_batched(x, w, u_bank, ids)


register("householder_gemm_batched", "pallas")(
    _registry_vjp("householder_gemm_batched", _hh_gemm_batched_pallas))


@register("etherplus_reflect_batched", "jnp")
def _ep_reflect_batched_jnp(x, u_bank, v_bank, ids):
    from repro.core.transforms import etherplus_activation_batched
    return etherplus_activation_batched(x, u_bank, v_bank, ids)


def _ep_reflect_batched_pallas(x, u_bank, v_bank, ids):
    from repro.kernels import ops
    return ops.etherplus_reflect_batched(x, u_bank, v_bank, ids)


register("etherplus_reflect_batched", "pallas")(
    _registry_vjp("etherplus_reflect_batched", _ep_reflect_batched_pallas))


@register("etherplus_merge", "jnp")
def _ep_merge_jnp(w, u1, v1, u2=None, v2=None):
    from repro.core.transforms import etherplus_weight
    out = etherplus_weight(w, u1, v1)
    if u2 is not None:
        out = etherplus_weight(out, u2, v2, side="right")
    return out


def _ep_merge_pallas(w, u1, v1, u2=None, v2=None):
    from repro.kernels import ops
    return ops.etherplus_merge(w, u1, v1, u2, v2)


register("etherplus_merge", "pallas")(
    _registry_vjp("etherplus_merge", _ep_merge_pallas))


# ---------------------------------------------------------------------------
# Backward ops (the ``*_bwd`` tier).  Signature: (*forward_primals, g) →
# cotangent tuple ordered like the primals.  jnp backend = ref-AD (XLA
# differentiating the jnp forward impl — exactly what the old
# _with_ref_vjp did for every shape); pallas backend = the hand-derived
# kernels in kernels/{reflect_bwd,gemm_bwd,reflect_bwd_batched,
# merge_bwd}.py.  Supports rules delegate to the forward op's rule: a
# shape the forward kernel tiles is a shape its backward tiles too.
# ---------------------------------------------------------------------------

register("ether_reflect_bwd", "jnp")(_ad_bwd(_reflect_jnp))


@register("ether_reflect_bwd", "pallas")
def _reflect_bwd_pallas(x, u, g):
    from repro.kernels import ops
    return ops.ether_reflect_bwd(x, u, g)


@supports_rule("ether_reflect_bwd")
def _sup_reflect_bwd(x, u, g):
    return _sup_reflect(x, u)


register("householder_gemm_bwd", "jnp")(_ad_bwd(_hh_gemm_jnp))


@register("householder_gemm_bwd", "pallas")
def _hh_gemm_bwd_pallas(x, w, u, g):
    from repro.kernels import ops
    return ops.householder_gemm_bwd(x, w, u, g)


@supports_rule("householder_gemm_bwd")
def _sup_hh_gemm_bwd(x, w, u, g):
    return _sup_hh_gemm(x, w, u)


register("ether_merge_bwd", "jnp")(_ad_bwd(_merge_jnp))


@register("ether_merge_bwd", "pallas")
def _merge_bwd_pallas(w, u, g):
    from repro.kernels import ops
    return ops.ether_merge_bwd(w, u, g)


@supports_rule("ether_merge_bwd")
def _sup_merge_bwd(w, u, g):
    return _sup_merge(w, u)


register("ether_reflect_batched_bwd", "jnp")(_ad_bwd(_reflect_batched_jnp))


@register("ether_reflect_batched_bwd", "pallas")
def _reflect_batched_bwd_pallas(x, u_bank, ids, g):
    from repro.kernels import ops
    return ops.ether_reflect_batched_bwd(x, u_bank, ids, g)


@supports_rule("ether_reflect_batched_bwd")
def _sup_reflect_batched_bwd(x, u_bank, ids, g):
    return _sup_reflect_batched(x, u_bank, ids)


register("etherplus_gemm_bwd", "jnp")(_ad_bwd(_ep_gemm_jnp))


@register("etherplus_gemm_bwd", "pallas")
def _ep_gemm_bwd_pallas(x, w, u1, v1, u2, v2, g):
    from repro.kernels import ops
    return ops.etherplus_gemm_bwd(x, w, u1, v1, u2, v2, g)


@supports_rule("etherplus_gemm_bwd")
def _sup_ep_gemm_bwd(x, w, u1, v1, u2, v2, g):
    return _sup_ep_gemm(x, w, u1, v1, u2, v2)


register("householder_gemm_batched_bwd", "jnp")(_ad_bwd(_hh_gemm_batched_jnp))


@register("householder_gemm_batched_bwd", "pallas")
def _hh_gemm_batched_bwd_pallas(x, w, u_bank, ids, g):
    from repro.kernels import ops
    return ops.householder_gemm_batched_bwd(x, w, u_bank, ids, g)


@supports_rule("householder_gemm_batched_bwd")
def _sup_hh_gemm_batched_bwd(x, w, u_bank, ids, g):
    return _sup_hh_gemm_batched(x, w, u_bank, ids)


register("etherplus_reflect_batched_bwd", "jnp")(
    _ad_bwd(_ep_reflect_batched_jnp))


@register("etherplus_reflect_batched_bwd", "pallas")
def _ep_reflect_batched_bwd_pallas(x, u_bank, v_bank, ids, g):
    from repro.kernels import ops
    return ops.etherplus_reflect_batched_bwd(x, u_bank, v_bank, ids, g)


@supports_rule("etherplus_reflect_batched_bwd")
def _sup_ep_reflect_batched_bwd(x, u_bank, v_bank, ids, g):
    return _sup_ep_reflect_batched(x, u_bank, v_bank, ids)


register("etherplus_merge_bwd", "jnp")(_ad_bwd(_ep_merge_jnp))


@register("etherplus_merge_bwd", "pallas")
def _ep_merge_bwd_pallas(w, u1, v1, u2, v2, g):
    from repro.kernels import ops
    return ops.etherplus_merge_bwd(w, u1, v1, u2, v2, g)


@supports_rule("etherplus_merge_bwd")
def _sup_ep_merge_bwd(w, u1, v1, u2, v2, g):
    return _sup_ep_merge(w, u1, v1, u2, v2)


# ---------------------------------------------------------------------------
# DeLoRA + HyperAdapt ops (DESIGN.md §15).  Registered through the same
# four-piece pattern as the reflection tier: jnp forward (transforms
# primitive), custom_vjp-wrapped pallas forward, ref-AD jnp backward,
# hand-derived pallas backward.  The backward supports rules check BOTH
# GEMM orientations — dx reuses the forward kernel on transposed
# operands (contracting over f, emitting d), so a shape must tile both
# ways before `auto` picks the kernel backward.
# ---------------------------------------------------------------------------

@supports_rule("delora_gemm")
def _sup_delora_gemm(x, w, a, b, s) -> bool:
    d, f = w.shape
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    if a.shape != (d, s.shape[-1]) or b.shape != (s.shape[-1], f):
        return False
    bm, bf, bk = gemm_tiles(t, d, f, 1)
    return bool(bm and bf and bk)


@supports_rule("delora_gemm_batched")
def _sup_delora_gemm_batched(x, w, a_bank, b_bank, s_bank, ids) -> bool:
    if x.ndim != 3 or a_bank.ndim != 3:
        return False
    _, s, d = x.shape
    _, f = w.shape
    if a_bank.shape[1] != d or b_bank.shape[-1] != f:
        return False
    bs, bf, bk = gemm_tiles(s, d, f, 1)
    return bool(bs and bf and bk)


@supports_rule("delora_merge")
def _sup_delora_merge(w, a, b, s) -> bool:
    return lane_ok(w.shape[-1])


@supports_rule("hyperadapt_gemm")
def _sup_ha_gemm(x, w, r, c) -> bool:
    d, f = w.shape
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    if r.shape != (d,) or c.shape != (f,):
        return False
    bm, bf, bk = gemm_tiles(t, d, f, 1)
    return bool(bm and bf and bk)


@supports_rule("hyperadapt_gemm_batched")
def _sup_ha_gemm_batched(x, w, r_bank, c_bank, ids) -> bool:
    if x.ndim != 3 or r_bank.ndim != 2:
        return False
    _, s, d = x.shape
    _, f = w.shape
    if r_bank.shape[-1] != d or c_bank.shape[-1] != f:
        return False
    bs, bf, bk = gemm_tiles(s, d, f, 1)
    return bool(bs and bf and bk)


@supports_rule("hyperadapt_merge")
def _sup_ha_merge(w, r, c) -> bool:
    return lane_ok(w.shape[-1])


@register("delora_gemm", "jnp")
def _delora_gemm_jnp(x, w, a, b, s):
    from repro.core.transforms import delora_gemm
    return delora_gemm(x, w, a, b, s)


def _delora_gemm_pallas(x, w, a, b, s):
    from repro.kernels import ops
    return ops.delora_gemm(x, w, a, b, s)


register("delora_gemm", "pallas")(
    _registry_vjp("delora_gemm", _delora_gemm_pallas))


@register("delora_gemm_batched", "jnp")
def _delora_gemm_batched_jnp(x, w, a_bank, b_bank, s_bank, ids):
    from repro.core.transforms import delora_gemm_batched
    return delora_gemm_batched(x, w, a_bank, b_bank, s_bank, ids)


def _delora_gemm_batched_pallas(x, w, a_bank, b_bank, s_bank, ids):
    from repro.kernels import ops
    return ops.delora_gemm_batched(x, w, a_bank, b_bank, s_bank, ids)


register("delora_gemm_batched", "pallas")(
    _registry_vjp("delora_gemm_batched", _delora_gemm_batched_pallas))


@register("delora_merge", "jnp")
def _delora_merge_jnp(w, a, b, s):
    from repro.core.transforms import delora_merge
    return delora_merge(w, a, b, s)


def _delora_merge_pallas(w, a, b, s):
    from repro.kernels import ops
    return ops.delora_merge(w, a, b, s)


register("delora_merge", "pallas")(
    _registry_vjp("delora_merge", _delora_merge_pallas))


@register("hyperadapt_gemm", "jnp")
def _ha_gemm_jnp(x, w, r, c):
    from repro.core.transforms import hyperadapt_gemm
    return hyperadapt_gemm(x, w, r, c)


def _ha_gemm_pallas(x, w, r, c):
    from repro.kernels import ops
    return ops.hyperadapt_gemm(x, w, r, c)


register("hyperadapt_gemm", "pallas")(
    _registry_vjp("hyperadapt_gemm", _ha_gemm_pallas))


@register("hyperadapt_gemm_batched", "jnp")
def _ha_gemm_batched_jnp(x, w, r_bank, c_bank, ids):
    from repro.core.transforms import hyperadapt_gemm_batched
    return hyperadapt_gemm_batched(x, w, r_bank, c_bank, ids)


def _ha_gemm_batched_pallas(x, w, r_bank, c_bank, ids):
    from repro.kernels import ops
    return ops.hyperadapt_gemm_batched(x, w, r_bank, c_bank, ids)


register("hyperadapt_gemm_batched", "pallas")(
    _registry_vjp("hyperadapt_gemm_batched", _ha_gemm_batched_pallas))


@register("hyperadapt_merge", "jnp")
def _ha_merge_jnp(w, r, c):
    from repro.core.transforms import hyperadapt_merge
    return hyperadapt_merge(w, r, c)


def _ha_merge_pallas(w, r, c):
    from repro.kernels import ops
    return ops.hyperadapt_merge(w, r, c)


register("hyperadapt_merge", "pallas")(
    _registry_vjp("hyperadapt_merge", _ha_merge_pallas))


register("delora_gemm_bwd", "jnp")(_ad_bwd(_delora_gemm_jnp))


@register("delora_gemm_bwd", "pallas")
def _delora_gemm_bwd_pallas(x, w, a, b, s, g):
    from repro.kernels import ops
    return ops.delora_gemm_bwd(x, w, a, b, s, g)


@supports_rule("delora_gemm_bwd")
def _sup_delora_gemm_bwd(x, w, a, b, s, g):
    d, f = w.shape
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    return (_sup_delora_gemm(x, w, a, b, s)
            and all(gemm_tiles(t, f, d, 1)))


register("delora_gemm_batched_bwd", "jnp")(_ad_bwd(_delora_gemm_batched_jnp))


@register("delora_gemm_batched_bwd", "pallas")
def _delora_gemm_batched_bwd_pallas(x, w, a_bank, b_bank, s_bank, ids, g):
    from repro.kernels import ops
    return ops.delora_gemm_batched_bwd(x, w, a_bank, b_bank, s_bank, ids, g)


@supports_rule("delora_gemm_batched_bwd")
def _sup_delora_gemm_batched_bwd(x, w, a_bank, b_bank, s_bank, ids, g):
    if x.ndim != 3:
        return False
    _, s, d = x.shape
    _, f = w.shape
    return (_sup_delora_gemm_batched(x, w, a_bank, b_bank, s_bank, ids)
            and all(gemm_tiles(s, f, d, 1)))


register("delora_merge_bwd", "jnp")(_ad_bwd(_delora_merge_jnp))


@register("delora_merge_bwd", "pallas")
def _delora_merge_bwd_pallas(w, a, b, s, g):
    from repro.kernels import ops
    return ops.delora_merge_bwd(w, a, b, s, g)


@supports_rule("delora_merge_bwd")
def _sup_delora_merge_bwd(w, a, b, s, g):
    return _sup_delora_merge(w, a, b, s)


register("hyperadapt_gemm_bwd", "jnp")(_ad_bwd(_ha_gemm_jnp))


@register("hyperadapt_gemm_bwd", "pallas")
def _ha_gemm_bwd_pallas(x, w, r, c, g):
    from repro.kernels import ops
    return ops.hyperadapt_gemm_bwd(x, w, r, c, g)


@supports_rule("hyperadapt_gemm_bwd")
def _sup_ha_gemm_bwd(x, w, r, c, g):
    d, f = w.shape
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    return (_sup_ha_gemm(x, w, r, c)
            and all(gemm_tiles(t, f, d, 1)))


register("hyperadapt_gemm_batched_bwd", "jnp")(_ad_bwd(_ha_gemm_batched_jnp))


@register("hyperadapt_gemm_batched_bwd", "pallas")
def _ha_gemm_batched_bwd_pallas(x, w, r_bank, c_bank, ids, g):
    from repro.kernels import ops
    return ops.hyperadapt_gemm_batched_bwd(x, w, r_bank, c_bank, ids, g)


@supports_rule("hyperadapt_gemm_batched_bwd")
def _sup_ha_gemm_batched_bwd(x, w, r_bank, c_bank, ids, g):
    if x.ndim != 3:
        return False
    _, s, d = x.shape
    _, f = w.shape
    return (_sup_ha_gemm_batched(x, w, r_bank, c_bank, ids)
            and all(gemm_tiles(s, f, d, 1)))


register("hyperadapt_merge_bwd", "jnp")(_ad_bwd(_ha_merge_jnp))


@register("hyperadapt_merge_bwd", "pallas")
def _ha_merge_bwd_pallas(w, r, c, g):
    from repro.kernels import ops
    return ops.hyperadapt_merge_bwd(w, r, c, g)


@supports_rule("hyperadapt_merge_bwd")
def _sup_ha_merge_bwd(w, r, c, g):
    return _sup_ha_merge(w, r, c)
