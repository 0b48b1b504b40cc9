"""Pallas TPU kernel: block-diagonal Householder reflection of activations.

This is the hot op of the *activation-side* ETHER execution mode
(DESIGN.md §3): ``H_B x = x − 2û(ûᵀx)`` applied blockwise on the feature
dim.  Cost O(tokens·d) — the GEMM that follows consumes the frozen weight
unchanged, so ETHER adds zero weight-side HBM traffic.

Tiling: tokens are tiled by ``block_t`` rows; the whole hyperplane
adapter rides along in VMEM as one flat (1, d) row (a few KB — ETHER
params are tiny by design; kernels/blockwise.py).  VMEM per step ≈
4·block_t·d·dtype + f32 temporaries; block_t=256, d=8192 needs the raised
scoped limit ``blockwise.ROW_VMEM``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blockwise as bw


def _reflect_kernel(u_ref, x_ref, o_ref, *, db: int):
    e = bw.block_matrix(x_ref.shape[1], db)
    un = bw.unit(u_ref[...].astype(jnp.float32), e)          # (1, d)
    out = bw.update(x_ref[...].astype(jnp.float32), [(un, -2.0)], e)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ether_reflect_pallas(x: jax.Array, u: jax.Array, *, block_t: int = 256,
                         interpret: bool | None = None) -> jax.Array:
    """x: (T, d) tokens; u: (n, db) with n*db == d. Returns H_B x.

    interpret=None auto-detects: compiled on TPU, emulated elsewhere
    (core.execute._interpret) — direct callers no longer silently run the
    Python interpreter on real hardware.
    """
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    t, d = x.shape
    n, db = u.shape
    assert n * db == d, (n, db, d)
    # Largest divisor of t that is <= block_t: direct callers and odd
    # decode shapes (t not a multiple of 256) must not crash — the grid
    # just gets more, smaller row-tiles.
    block_t = largest_divisor(t, block_t)
    grid = (t // block_t,)
    return pl.pallas_call(
        functools.partial(_reflect_kernel, db=db),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),          # whole adapter
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        compiler_params=bw.ROW_VMEM,
        interpret=interpret,
    )(u.reshape(1, d), x)
