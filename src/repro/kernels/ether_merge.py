"""Pallas TPU kernel: weight-side block-diagonal reflection W' = H_B W.

Used for merging adapters at deployment (zero-latency serving) and as the
paper-faithful weight-side training mode. One grid step processes one
(db × Tf) tile of W with its block's hyperplane vector: the rank-1 update
``W_i − 2û_i(û_iᵀW_i)`` — O(d·f) total, independent of n (DESIGN.md §3,
"Identity 2").  The block's vector rides as a (db, 1) column, so every
tile is (8, 128)-aligned whenever db % 8 == 0 and Tf % 128 == 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _merge_kernel(u_ref, w_ref, o_ref):
    u = u_ref[...].astype(jnp.float32)                       # (db, 1)
    un = u / (jnp.sqrt(jnp.sum(u * u)) + 1e-8)
    w = w_ref[...].astype(jnp.float32)                       # (db, Tf)
    proj = jnp.sum(un * w, axis=0, keepdims=True)            # ûᵀW_i: (1, Tf)
    o_ref[...] = (w - 2.0 * un * proj).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def ether_merge_pallas(w: jax.Array, u: jax.Array, *, block_f: int = 512,
                       interpret: bool | None = None) -> jax.Array:
    """w: (d, f); u: (n, db), n*db == d. Returns H_B w.

    interpret=None auto-detects via core.execute._interpret."""
    from repro.core.execute import _interpret
    interpret = _interpret(interpret)
    d, f = w.shape
    n, db = u.shape
    assert n * db == d
    block_f = min(block_f, f)
    assert f % block_f == 0
    grid = (n, f // block_f)
    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((db, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((db, block_f), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((db, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, f), w.dtype),
        interpret=interpret,
    )(u.reshape(d, 1), w)
