"""Pallas TPU kernels: ETHER+ weight absorption W' = H⁺_L W H̃⁺_R.

Merged-deployment counterpart of ``ether_merge`` for the rank-2 variant
(satellite of the fused-GEMM tier): the left kernel applies the blockwise
rank-2 update on the input dim (one grid step = one (db × Tf) tile of W
with its block's u/v pair), the right kernel applies it on the output
dim (one grid step = one (Td × Tf) tile of whole output blocks, the
u/v pair riding flat as in the fused GEMM epilogue).  O(d·f) each, independent
of n — same accounting as the rank-1 merge ("Identity 2", DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.etherplus_gemm import _rank2


def _merge_left_kernel(u_ref, v_ref, w_ref, o_ref):
    u = u_ref[...].astype(jnp.float32)                       # (db, 1)
    v = v_ref[...].astype(jnp.float32)
    un = u / (jnp.sqrt(jnp.sum(u * u)) + 1e-8)
    vn = v / (jnp.sqrt(jnp.sum(v * v)) + 1e-8)
    w = w_ref[...].astype(jnp.float32)                       # (db, Tf)
    pu = jnp.sum(un * w, axis=0, keepdims=True)              # (1, Tf)
    pv = jnp.sum(vn * w, axis=0, keepdims=True)
    o_ref[...] = (w - un * pu + vn * pv).astype(o_ref.dtype)


def _merge_right_kernel(u_ref, v_ref, w_ref, o_ref, *, db: int):
    w = w_ref[...].astype(jnp.float32)                       # (Td, Tf)
    o_ref[...] = _rank2(w, u_ref[...], v_ref[...], db).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def etherplus_merge_left_pallas(w: jax.Array, u: jax.Array, v: jax.Array,
                                *, block_f: int = 512,
                                interpret: bool | None = None) -> jax.Array:
    """w: (d, f); u/v: (n, db), n*db == d. Returns H⁺_B w."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    d, f = w.shape
    n, db = u.shape
    assert n * db == d and u.shape == v.shape
    # lane-aligned tile when f allows it (TPU requirement); the
    # largest-divisor shrink is an interpret-only escape hatch.
    if f % 512 == 0:
        block_f = min(block_f, 512)
    elif f % 128 == 0:
        block_f = min(block_f, 128)
    else:
        block_f = largest_divisor(f, block_f)
    grid = (n, f // block_f)
    return pl.pallas_call(
        _merge_left_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((db, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((db, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((db, block_f), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((db, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, f), w.dtype),
        interpret=interpret,
    )(u.reshape(d, 1), v.reshape(d, 1), w)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def etherplus_merge_right_pallas(w: jax.Array, u: jax.Array, v: jax.Array,
                                 *, block_d: int = 256,
                                 interpret: bool | None = None) -> jax.Array:
    """w: (d, f); u/v: (n_out, db_out), n_out*db_out == f. Returns w H̃⁺_B.

    The rows of W are reflected like activations: (Td, Tf) tiles with
    Tf a multiple of db_out (128-lane aligned when f allows it)."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    d, f = w.shape
    n, db = u.shape
    assert n * db == f and u.shape == v.shape
    block_d = largest_divisor(d, block_d)
    block_f = next((bf for bf in (512, 256, 128)
                    if f % bf == 0 and bf % db == 0), f)
    grid = (d // block_d, f // block_f)
    row_spec = pl.BlockSpec((1, block_f), lambda i, j: (0, j))
    return pl.pallas_call(
        functools.partial(_merge_right_kernel, db=db),
        grid=grid,
        in_specs=[row_spec, row_spec,
                  pl.BlockSpec((block_d, block_f), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_d, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, f), w.dtype),
        interpret=interpret,
    )(u.reshape(1, f), v.reshape(1, f), w)
