"""Pallas TPU kernels: backwards for the weight-side merges.

Left merge (rank-1 ether_merge / rank-2 etherplus left factor), per
input block i with W_i: (db, f):

    Y_i = W_i + c_u û(ûᵀW_i) [+ c_v v̂(v̂ᵀW_i)]
    dW_i   = G_i + c_u û(ûᵀG_i) [+ c_v v̂(v̂ᵀG_i)]       (symmetric)
    dL/dû = c_u [ G_i (W_iᵀû) + W_i (G_iᵀû) ]            (→ ε-norm chain)

Right merge (ETHER+ H̃⁺ factor), per output block j with W_j: (d, db):

    Y_j = W_j + c_u (W_j û)ûᵀ [+ c_v (W_j v̂)v̂ᵀ]
    dW_j   = G_j + c_u (G_j û)ûᵀ [+ ...]
    dL/dû = c_u [ G_jᵀ(W_j û) + W_jᵀ(G_j û) ]

Grids mirror the forward merge kernels: (n, F/Tf) left, with the
block's vector as a (db, 1) column and its dL/dû accumulating in a
(db, 1) f32 scratch over the trailing axis; (F/Tf, D/Td) right, where
W's rows are reflected like activations (kernels/blockwise.py) and the
F-tile's dL/dû accumulates over the row tiles.  The chain rule is
applied at each accumulator's last tile.  O(d·f) like the forward —
the merge backward costs one extra pass over W and G, nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw
from repro.kernels.reflect_bwd import norm_chain


def _unit_col(u):
    """(db, 1) f32 column -> unit column (matches the forward merges)."""
    return u / (jnp.sqrt(jnp.sum(u * u)) + 1e-8)


def _left_dir(un, w, g, coeff):
    """One direction's (dW term, ĝ) for a left-merge tile.

    un: (db, 1); w/g: (db, Tf) f32."""
    pw = jnp.sum(un * w, axis=0, keepdims=True)       # ûᵀW_i: (1, Tf)
    pg = jnp.sum(un * g, axis=0, keepdims=True)       # ûᵀG_i: (1, Tf)
    dw_term = coeff * un * pg
    ghat = coeff * (jnp.sum(g * pw, axis=1, keepdims=True)
                    + jnp.sum(w * pg, axis=1, keepdims=True))   # (db, 1)
    return dw_term, ghat


def _merge_left_bwd_kernel(*refs, coeffs):
    """refs = (*adapter columns, w, g, dw, *du outs, *accs)."""
    m = len(coeffs)
    u_refs, (w_ref, g_ref, dw_ref) = refs[:m], refs[m:m + 3]
    du_refs, accs = refs[m + 3:2 * m + 3], refs[2 * m + 3:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    dw = g
    for u_ref, acc, coeff in zip(u_refs, accs, coeffs):
        term, ghat = _left_dir(_unit_col(u_ref[...].astype(jnp.float32)),
                               w, g, coeff)
        dw = dw + term
        acc[...] += ghat
    dw_ref[...] = dw.astype(dw_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        for u_ref, out, acc in zip(u_refs, du_refs, accs):
            out[...] = norm_chain(u_ref[...].astype(jnp.float32), acc[...],
                                  axis=0).astype(out.dtype)


def _merge_right_bwd_kernel(u_ref, v_ref, w_ref, g_ref, dw_ref, du_ref,
                            dv_ref, accu_ref, accv_ref, *, db: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        accu_ref[...] = jnp.zeros_like(accu_ref)
        accv_ref[...] = jnp.zeros_like(accv_ref)

    e = bw.block_matrix(w_ref.shape[1], db)
    u = u_ref[...].astype(jnp.float32)                # (1, Tf)
    v = v_ref[...].astype(jnp.float32)
    dw, (ghu, ghv) = bw.update_bwd(
        w_ref[...].astype(jnp.float32), g_ref[...].astype(jnp.float32),
        [(bw.unit(u, e), -1.0), (bw.unit(v, e), 1.0)], e)
    dw_ref[...] = dw.astype(dw_ref.dtype)
    accu_ref[...] += ghu
    accv_ref[...] += ghv

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        du_ref[...] = bw.norm_chain(u, accu_ref[...], e).astype(du_ref.dtype)
        dv_ref[...] = bw.norm_chain(v, accv_ref[...], e).astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def merge_left_bwd_pallas(w: jax.Array, u: jax.Array, g: jax.Array,
                          v: jax.Array | None = None, *,
                          block_f: int = 512,
                          interpret: bool | None = None):
    """(dw, du[, dv]) for the left merge.  w/g: (d, f); u[/v]: (n, db)."""
    from repro.core.execute import _interpret, largest_divisor
    d, f = w.shape
    n, db = u.shape
    assert n * db == d and g.shape == w.shape
    block_f = largest_divisor(f, block_f)
    adapters = (u,) if v is None else (u, v)
    m = len(adapters)
    col = pl.BlockSpec((db, 1), lambda i, j: (i, 0))
    tile = pl.BlockSpec((db, block_f), lambda i, j: (i, j))
    outs = pl.pallas_call(
        functools.partial(_merge_left_bwd_kernel,
                          coeffs=(-2.0,) if v is None else (-1.0, 1.0)),
        grid=(n, f // block_f),
        in_specs=[col] * m + [tile, tile],
        out_specs=[tile] + [col] * m,
        out_shape=[jax.ShapeDtypeStruct((d, f), w.dtype)]
        + [jax.ShapeDtypeStruct((d, 1), a.dtype) for a in adapters],
        scratch_shapes=[pltpu.VMEM((db, 1), jnp.float32)] * m,
        interpret=_interpret(interpret),
    )(*(a.reshape(d, 1) for a in adapters), w, g)
    return (outs[0],) + tuple(o.reshape(n, db) for o in outs[1:])


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def merge_right_bwd_pallas(w: jax.Array, u: jax.Array, v: jax.Array,
                           g: jax.Array, *, block_d: int = 256,
                           interpret: bool | None = None):
    """(dw, du, dv) for the rank-2 right merge.  w/g: (d, f);
    u/v: (n_out, db_out), n_out*db_out == f."""
    from repro.core.execute import _interpret, largest_divisor
    d, f = w.shape
    n, db = u.shape
    assert n * db == f and u.shape == v.shape and g.shape == w.shape
    block_d = largest_divisor(d, block_d)
    block_f = next((bf for bf in (512, 256, 128)
                    if f % bf == 0 and bf % db == 0), f)
    row = pl.BlockSpec((1, block_f), lambda j, i: (0, j))
    tile = pl.BlockSpec((block_d, block_f), lambda j, i: (i, j))
    dw, du, dv = pl.pallas_call(
        functools.partial(_merge_right_bwd_kernel, db=db),
        grid=(f // block_f, d // block_d),
        in_specs=[row, row, tile, tile],
        out_specs=[tile, row, row],
        out_shape=[jax.ShapeDtypeStruct((d, f), w.dtype),
                   jax.ShapeDtypeStruct((1, f), u.dtype),
                   jax.ShapeDtypeStruct((1, f), v.dtype)],
        scratch_shapes=[pltpu.VMEM((1, block_f), jnp.float32)] * 2,
        interpret=_interpret(interpret),
    )(u.reshape(1, f), v.reshape(1, f), w, g)
    return dw, du.reshape(n, db), dv.reshape(n, db)
