"""Pallas TPU kernels: hand-derived backwards for the token reflections.

Pallas has no autodiff (and interpret mode's AD raises outright), so the
kernel-backed training path needs explicit backward kernels.  ETHER's
multiplicative structure makes them cheap to derive: with û = u/(‖u‖+ε)
and the blockwise generalized update

    y = x + c_u û(ûᵀx) [+ c_v v̂(v̂ᵀx)]            (rank-1: c_u = −2;
                                                   ETHER+: c_u=−1, c_v=+1)

the operator is symmetric, so for a cotangent G:

    dx   = G + c_u û(ûᵀG) [+ c_v v̂(v̂ᵀG)]          (reapply the transform)
    dL/dû = c_u Σ_t [ (ûᵀx_t) G_t + (ûᵀG_t) x_t ]   (and likewise for v̂)
    du   = dL/dû/s − (u·dL/dû) u/(r s²),  r = ‖u‖, s = r + ε

i.e. the backward reuses the forward's normalized directions as its only
residuals — no intermediate activations are saved, and nothing is
re-derived by differentiating the jnp reference.

Grid: (T/block_t,).  dx is tile-local; dL/dû accumulates in a persistent
f32 (1, d) VMEM scratch across all row tiles (the TPU grid is sequential
on a core) and the ε-normalization chain rule is applied once at the
final step.  The in-kernel math is ``blockwise.update_bwd`` on flat
tiles; ``norm_chain`` below is its (..., n, db) twin for jnp callers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw


def norm_chain(u, ghat, eps: float = 1e-8, axis: int = -1):
    """Pull dL/dû back through û = u/(‖u‖+ε) along ``axis`` (f32).

    This is exactly XLA's AD of the reference normalization, so kernel
    backwards that use it agree with ref-AD to rounding error."""
    r = jnp.sqrt(jnp.sum(u * u, axis=axis, keepdims=True))
    s = r + eps
    dot = jnp.sum(u * ghat, axis=axis, keepdims=True)
    return ghat / s - dot * u / (r * s * s)


def _r1_bwd_kernel(u_ref, x_ref, g_ref, dx_ref, du_ref, acc_ref, *,
                   db: int, coeff: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    e = bw.block_matrix(x_ref.shape[1], db)
    u = u_ref[...].astype(jnp.float32)                       # (1, d)
    dx, (ghat,) = bw.update_bwd(x_ref[...].astype(jnp.float32),
                                g_ref[...].astype(jnp.float32),
                                [(bw.unit(u, e), coeff)], e)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    acc_ref[...] += ghat

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        du_ref[...] = bw.norm_chain(u, acc_ref[...], e).astype(du_ref.dtype)


def _r2_bwd_kernel(u_ref, v_ref, x_ref, g_ref, dx_ref, du_ref, dv_ref,
                   accu_ref, accv_ref, *, db: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        accu_ref[...] = jnp.zeros_like(accu_ref)
        accv_ref[...] = jnp.zeros_like(accv_ref)

    e = bw.block_matrix(x_ref.shape[1], db)
    u = u_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    dx, (ghu, ghv) = bw.update_bwd(
        x_ref[...].astype(jnp.float32), g_ref[...].astype(jnp.float32),
        [(bw.unit(u, e), -1.0), (bw.unit(v, e), 1.0)], e)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    accu_ref[...] += ghu
    accv_ref[...] += ghv

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        du_ref[...] = bw.norm_chain(u, accu_ref[...], e).astype(du_ref.dtype)
        dv_ref[...] = bw.norm_chain(v, accv_ref[...], e).astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ether_reflect_bwd_pallas(x: jax.Array, u: jax.Array, g: jax.Array, *,
                             block_t: int = 256,
                             interpret: bool | None = None):
    """x/g: (T, d); u: (n, db), n*db == d. Returns (dx, du)."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    t, d = x.shape
    n, db = u.shape
    assert n * db == d and g.shape == x.shape
    block_t = largest_divisor(t, block_t)
    grid = (t // block_t,)
    dx, du = pl.pallas_call(
        functools.partial(_r1_bwd_kernel, db=db, coeff=-2.0),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((1, d), u.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=bw.ROW_VMEM,
        interpret=interpret,
    )(u.reshape(1, d), x, g)
    return dx, du.reshape(n, db)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def etherplus_reflect_bwd_pallas(x: jax.Array, u: jax.Array, v: jax.Array,
                                 g: jax.Array, *, block_t: int = 256,
                                 interpret: bool | None = None):
    """Rank-2 H⁺ backward. x/g: (T, d); u/v: (n, db). → (dx, du, dv)."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    t, d = x.shape
    n, db = u.shape
    assert n * db == d and u.shape == v.shape and g.shape == x.shape
    block_t = largest_divisor(t, block_t)
    grid = (t // block_t,)
    row = pl.BlockSpec((1, d), lambda i: (0, 0))
    dx, du, dv = pl.pallas_call(
        functools.partial(_r2_bwd_kernel, db=db),
        grid=grid,
        in_specs=[
            row, row,
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((block_t, d), lambda i: (i, 0)), row, row],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((1, d), u.dtype),
            jax.ShapeDtypeStruct((1, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=bw.ROW_VMEM,
        interpret=interpret,
    )(u.reshape(1, d), v.reshape(1, d), x, g)
    return dx, du.reshape(n, db), dv.reshape(n, db)
