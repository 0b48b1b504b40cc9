"""Pallas TPU kernels: hand-derived backwards for the fused reflect-GEMMs.

Forward (householder_gemm / etherplus_gemm input side):

    y = R(x) @ W,   R = blockwise (I + c_u ûûᵀ [+ c_v v̂v̂ᵀ])

Backward under cotangent G, with dXr = G @ Wᵀ:

    dx = R(dXr)                      (R symmetric)
    dL/dû = c_u Σ_t [ (ûᵀx_t) dXr_t + (ûᵀdXr_t) x_t ]   (→ ε-norm chain)
    dW = R(x)ᵀ @ G                   (frozen-weight cotangent)

Two fused passes instead of one: dx+du share the dXr GEMM so they live
in one kernel (grid (D/Td, M/Tm, F/Tf), F innermost accumulating dXr in
f32 scratch; the reflection backward runs on the finished dXr tile and
dL/dû for the D-tile accumulates in a (1, Td) scratch over all row
tiles, so the du output block is finished before the D-tile moves on).
dW is a *separate* pallas_call so XLA can dead-code it when the base
weight is frozen — the common PEFT case pays nothing for it.
Constraint: Td holds whole reflection blocks (Td % db == 0), mirroring
the forward's Tk rule; ops.py enforces/falls back.  Adapters ride flat
as (1, Td) slices (kernels/blockwise.py).

The batched bank variants add a leading (B,) grid axis with
scalar-prefetch tenant-id gathers (see householder_gemm_batched) and
emit *per-sequence* un-normalized dL/dû partials — the wrapper
scatter-adds them into the bank and applies the chain rule once per
bank row, which is what makes duplicate tenant ids accumulate exactly
like ref-AD's gather vjp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw


def _dirs(rows, coeffs, e):
    """Unit (1, Td) rows for every (raw adapter row, coeff) direction."""
    return [(bw.unit(r.astype(jnp.float32), e), c)
            for r, c in zip(rows, coeffs)]


def _coeffs(v):
    """Rank-1 Householder (coeff −2) or ETHER+ rank-2 (−1 / +1)."""
    return (-2.0,) if v is None else (-1.0, 1.0)


def _block_d(d: int, db: int, block_d: int) -> int:
    block_d = min(block_d, d)
    if block_d % db:
        block_d = db * max(1, block_d // db)
    assert d % block_d == 0, "caller guarantees whole K-blocks (ops.py)"
    return block_d


def _gemm_dx_kernel(*refs, db: int, coeffs, batched: bool):
    """refs = ([ids,] *adapter rows, x, w, g, dx, *du outs, acc,
    *du accs).  Grid (D/Td, M/Tm, F/Tf) — batched: (B, D/Td, S/Ts, F/Tf).
    The single-tenant du outs are finished dL/du; the batched ones are
    the sequence's un-normalized dL/dû (the wrapper scatter-adds them
    into the bank and applies the chain rule per bank row)."""
    m = len(coeffs)
    refs = refs[1:] if batched else refs
    u_refs, (x_ref, w_ref, g_ref, dx_ref) = refs[:m], refs[m:m + 4]
    du_refs, acc_ref = refs[m + 4:2 * m + 4], refs[2 * m + 4]
    du_accs = refs[2 * m + 5:]
    ax = 1 if batched else 0
    i, f = pl.program_id(ax + 1), pl.program_id(ax + 2)
    nf = pl.num_programs(ax + 2)
    lead = (lambda r: r[0]) if batched else (lambda r: r[...])

    @pl.when(f == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((i == 0) & (f == 0))
    def _init_du():
        for acc in du_accs:
            acc[...] = jnp.zeros_like(acc)

    # dXr tile accumulation: G (Tm, Tf) · Wᵀ (Tf, Td)
    acc_ref[...] += bw.matmul(lead(g_ref), w_ref[...], ((1,), (1,)))

    @pl.when(f == nf - 1)
    def _finish_tile():
        e = bw.block_matrix(acc_ref.shape[1], db)
        dirs = _dirs([lead(r) for r in u_refs], coeffs, e)
        dx, ghats = bw.update_bwd(lead(x_ref).astype(jnp.float32),
                                  acc_ref[...], dirs, e)
        if batched:
            dx_ref[0] = dx.astype(dx_ref.dtype)
        else:
            dx_ref[...] = dx.astype(dx_ref.dtype)
        for acc, ghat in zip(du_accs, ghats):
            acc[...] += ghat

        @pl.when(i == pl.num_programs(ax + 1) - 1)
        def _emit_du():
            for u_ref, out, acc in zip(u_refs, du_refs, du_accs):
                if batched:
                    out[0] = acc[...]
                else:
                    u = u_ref[...].astype(jnp.float32)
                    out[...] = bw.norm_chain(u, acc[...], e).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_d",
                                             "block_f", "interpret"))
def reflect_gemm_dx_pallas(x: jax.Array, w: jax.Array, u: jax.Array,
                           g: jax.Array, v: jax.Array | None = None, *,
                           block_m: int = 128, block_d: int = 512,
                           block_f: int = 128,
                           interpret: bool | None = None):
    """Fused (dx, du[, dv]) for y = R(x) @ w under cotangent g.

    x: (T, d); w: (d, f); u[/v]: (n, db); g: (T, f).  Rank-1 Householder
    when v is None (coeff −2), ETHER+ rank-2 otherwise (−1/+1)."""
    from repro.core.execute import _interpret, largest_divisor
    t, d = x.shape
    d2, f = w.shape
    n, db = u.shape
    assert d == d2 and n * db == d and g.shape == (t, f)
    block_m = largest_divisor(t, block_m)
    block_f = largest_divisor(f, block_f)
    block_d = _block_d(d, db, block_d)
    adapters = (u,) if v is None else (u, v)
    m = len(adapters)
    row = pl.BlockSpec((1, block_d), lambda k, i, f: (0, k))
    outs = pl.pallas_call(
        functools.partial(_gemm_dx_kernel, db=db, coeffs=_coeffs(v),
                          batched=False),
        grid=(d // block_d, t // block_m, f // block_f),
        in_specs=[row] * m + [
            pl.BlockSpec((block_m, block_d), lambda k, i, f: (i, k)),   # x
            pl.BlockSpec((block_d, block_f), lambda k, i, f: (k, f)),   # w
            pl.BlockSpec((block_m, block_f), lambda k, i, f: (i, f)),   # g
        ],
        out_specs=[pl.BlockSpec((block_m, block_d), lambda k, i, f: (i, k))]
        + [row] * m,
        out_shape=[jax.ShapeDtypeStruct((t, d), x.dtype)]
        + [jax.ShapeDtypeStruct((1, d), a.dtype) for a in adapters],
        scratch_shapes=[pltpu.VMEM((block_m, block_d), jnp.float32)]
        + [pltpu.VMEM((1, block_d), jnp.float32)] * m,
        interpret=_interpret(interpret),
    )(*(a.reshape(1, d) for a in adapters), x, w, g)
    return (outs[0],) + tuple(o.reshape(n, db) for o in outs[1:])


# ---------------------------------------------------------------------------
# dW = R(x)ᵀ @ G — separate pass so frozen-weight training DCEs it
# ---------------------------------------------------------------------------

def _gemm_dw_kernel(*refs, db: int, coeffs, batched: bool):
    """refs = ([ids,] *adapter rows, x, g, dw, acc).  Grid (D/Td, F/Tf,
    M/Tm) — batched: (D/Td, F/Tf, B, S/Ts); the trailing axes reduce."""
    m = len(coeffs)
    refs = refs[1:] if batched else refs
    u_refs, (x_ref, g_ref, dw_ref, acc_ref) = refs[:m], refs[m:]
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    if batched:
        first &= pl.program_id(3) == 0
        last &= pl.program_id(3) == pl.num_programs(3) - 1
    lead = (lambda r: r[0]) if batched else (lambda r: r[...])

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = lead(x_ref)
    e = bw.block_matrix(x.shape[1], db)
    xr = bw.update(x.astype(jnp.float32),
                   _dirs([lead(r) for r in u_refs], coeffs, e), e)
    acc_ref[...] += bw.matmul(xr.astype(x.dtype), lead(g_ref), ((0,), (0,)))

    @pl.when(last)
    def _done():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_d",
                                             "block_f", "w_dtype",
                                             "interpret"))
def reflect_gemm_dw_pallas(x: jax.Array, u: jax.Array, g: jax.Array,
                           v: jax.Array | None = None, *,
                           block_m: int = 128, block_d: int = 512,
                           block_f: int = 128, w_dtype=None,
                           interpret: bool | None = None) -> jax.Array:
    """dw = R(x)ᵀ @ g.  x: (T, d); g: (T, f); u[/v]: (n, db)."""
    from repro.core.execute import _interpret, largest_divisor
    t, d = x.shape
    t2, f = g.shape
    n, db = u.shape
    assert t == t2 and n * db == d
    block_m = largest_divisor(t, block_m)
    block_f = largest_divisor(f, block_f)
    block_d = _block_d(d, db, block_d)
    adapters = (u,) if v is None else (u, v)
    row = pl.BlockSpec((1, block_d), lambda k, j, t: (0, k))
    return pl.pallas_call(
        functools.partial(_gemm_dw_kernel, db=db, coeffs=_coeffs(v),
                          batched=False),
        grid=(d // block_d, f // block_f, t // block_m),
        in_specs=[row] * len(adapters) + [
            pl.BlockSpec((block_m, block_d), lambda k, j, t: (t, k)),   # x
            pl.BlockSpec((block_m, block_f), lambda k, j, t: (t, j)),   # g
        ],
        out_specs=pl.BlockSpec((block_d, block_f), lambda k, j, t: (k, j)),
        out_shape=jax.ShapeDtypeStruct((d, f), w_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, block_f), jnp.float32)],
        interpret=_interpret(interpret),
    )(*(a.reshape(1, d) for a in adapters), x, g)


# ---------------------------------------------------------------------------
# Batched bank variants (multi-tenant training)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_s", "block_d",
                                             "block_f", "interpret"))
def householder_gemm_batched_bwd_pallas(x: jax.Array, w: jax.Array,
                                        u_bank: jax.Array, ids: jax.Array,
                                        g: jax.Array, *, block_s: int = 128,
                                        block_d: int = 512,
                                        block_f: int = 128,
                                        interpret: bool | None = None):
    """(dx, ĝ_seq) for the fused bank GEMM.  x: (B, S, d); w: (d, f);
    u_bank: (A, n, db); ids: (B,); g: (B, S, f).  ĝ_seq: (B, n, db) f32
    per-sequence un-normalized dL/dû partials."""
    from repro.core.execute import _interpret, largest_divisor
    b, s, d = x.shape
    d2, f = w.shape
    a, n, db = u_bank.shape
    assert d == d2 and n * db == d and g.shape == (b, s, f)
    block_s = largest_divisor(s, block_s)
    block_f = largest_divisor(f, block_f)
    block_d = _block_d(d, db, block_d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, d // block_d, s // block_s, f // block_f),
        in_specs=[
            pl.BlockSpec((1, 1, block_d),
                         lambda i, k, j, f, ids_ref: (ids_ref[i], 0, k)),
            pl.BlockSpec((1, block_s, block_d),
                         lambda i, k, j, f, ids_ref: (i, j, k)),
            pl.BlockSpec((block_d, block_f),
                         lambda i, k, j, f, ids_ref: (k, f)),
            pl.BlockSpec((1, block_s, block_f),
                         lambda i, k, j, f, ids_ref: (i, j, f)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_d),
                         lambda i, k, j, f, ids_ref: (i, j, k)),
            pl.BlockSpec((1, 1, block_d),
                         lambda i, k, j, f, ids_ref: (i, 0, k)),
        ],
        scratch_shapes=[pltpu.VMEM((block_s, block_d), jnp.float32),
                        pltpu.VMEM((1, block_d), jnp.float32)],
    )
    dx, ghat = pl.pallas_call(
        functools.partial(_gemm_dx_kernel, db=db, coeffs=(-2.0,),
                          batched=True),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, s, d), x.dtype),
                   jax.ShapeDtypeStruct((b, 1, d), jnp.float32)],
        interpret=_interpret(interpret),
    )(ids.astype(jnp.int32), u_bank.reshape(a, 1, d), x, w, g)
    return dx, ghat.reshape(b, n, db)


@functools.partial(jax.jit, static_argnames=("block_s", "block_d",
                                             "block_f", "w_dtype",
                                             "interpret"))
def householder_gemm_batched_dw_pallas(x: jax.Array, u_bank: jax.Array,
                                       ids: jax.Array, g: jax.Array, *,
                                       block_s: int = 128,
                                       block_d: int = 512,
                                       block_f: int = 128, w_dtype=None,
                                       interpret: bool | None = None
                                       ) -> jax.Array:
    """dw = Σ_b R_b(x_b)ᵀ @ g_b (shared frozen weight, per-tenant R)."""
    from repro.core.execute import _interpret, largest_divisor
    b, s, d = x.shape
    a, n, db = u_bank.shape
    f = g.shape[-1]
    assert n * db == d and g.shape[:2] == (b, s)
    block_s = largest_divisor(s, block_s)
    block_f = largest_divisor(f, block_f)
    block_d = _block_d(d, db, block_d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // block_d, f // block_f, b, s // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, block_d),
                         lambda k, jf, i, j, ids_ref: (ids_ref[i], 0, k)),
            pl.BlockSpec((1, block_s, block_d),
                         lambda k, jf, i, j, ids_ref: (i, j, k)),
            pl.BlockSpec((1, block_s, block_f),
                         lambda k, jf, i, j, ids_ref: (i, j, jf)),
        ],
        out_specs=pl.BlockSpec((block_d, block_f),
                               lambda k, jf, i, j, ids_ref: (k, jf)),
        scratch_shapes=[pltpu.VMEM((block_d, block_f), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gemm_dw_kernel, db=db, coeffs=(-2.0,),
                          batched=True),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, f), w_dtype or x.dtype),
        interpret=_interpret(interpret),
    )(ids.astype(jnp.int32), u_bank.reshape(a, 1, d), x, g)
