"""Pallas TPU kernels: backwards for the per-tenant bank reflections.

The batched analogues of ``reflect_bwd``: every sequence gathers its
tenant's hyperplane vectors via scalar-prefetch indexing (same indexed
DMA as the forward), computes its tile-local dx, and accumulates a
*per-sequence* un-normalized dL/dû partial over its S tiles.  The bank
cotangent is finished by the ops.py wrapper:

    du_bank = norm_chain(u_bank, zeros.at[ids].add(ĝ_seq))

scatter-add first, ε-normalization chain second — valid because the
chain rule is linear in dL/dû and all sequences with the same tenant id
share one bank row.  This reproduces ref-AD's gather-vjp exactly, so
duplicate tenant ids accumulate rather than overwrite.

Grid: (B, S/block_s).  The banks ride flat as (A, 1, d) rows; ĝ_seq
rides in a persistent (1, d) f32 scratch, re-zeroed at each sequence's
first S tile and emitted at its last.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw


def _bank_bwd_kernel(ids_ref, *refs, db: int, coeffs):
    """Shared body: refs = (*adapter rows, x, g, dx, *ĝ outs, *accs),
    one adapter row / ĝ output / accumulator per (direction, coeff)."""
    del ids_ref  # consumed by the index maps
    m = len(coeffs)
    u_refs, (x_ref, g_ref, dx_ref) = refs[:m], refs[m:m + 3]
    gu_refs, acc_refs = refs[m + 3:2 * m + 3], refs[2 * m + 3:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for acc in acc_refs:
            acc[...] = jnp.zeros_like(acc)

    e = bw.block_matrix(x_ref.shape[2], db)
    dirs = [(bw.unit(r[0].astype(jnp.float32), e), c)
            for r, c in zip(u_refs, coeffs)]
    dx, ghats = bw.update_bwd(x_ref[0].astype(jnp.float32),
                              g_ref[0].astype(jnp.float32), dirs, e)
    dx_ref[0] = dx.astype(dx_ref.dtype)
    for acc, ghat in zip(acc_refs, ghats):
        acc[...] += ghat

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        for out, acc in zip(gu_refs, acc_refs):
            out[0] = acc[...]


def _bank_bwd(x, banks, ids, g, coeffs, block_s, interpret):
    """dx and per-sequence ĝ partials (B, n, db) for each bank."""
    from repro.core.execute import _interpret, largest_divisor
    b, s, d = x.shape
    a, n, db = banks[0].shape
    assert n * db == d and g.shape == x.shape
    assert all(bk.shape == banks[0].shape for bk in banks)
    block_s = largest_divisor(s, block_s)
    row_in = pl.BlockSpec((1, 1, d), lambda i, j, ids_ref: (ids_ref[i], 0, 0))
    row_out = pl.BlockSpec((1, 1, d), lambda i, j, ids_ref: (i, 0, 0))
    tile = pl.BlockSpec((1, block_s, d), lambda i, j, ids_ref: (i, j, 0))
    m = len(banks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // block_s),
        in_specs=[row_in] * m + [tile, tile],
        out_specs=[tile] + [row_out] * m,
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)] * m,
    )
    dx, *ghats = pl.pallas_call(
        functools.partial(_bank_bwd_kernel, db=db, coeffs=coeffs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, s, d), x.dtype)]
        + [jax.ShapeDtypeStruct((b, 1, d), jnp.float32)] * m,
        compiler_params=bw.ROW_VMEM,
        interpret=_interpret(interpret),
    )(ids.astype(jnp.int32), *(bk.reshape(a, 1, d) for bk in banks), x, g)
    return (dx, *(gh.reshape(b, n, db) for gh in ghats))


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def ether_reflect_batched_bwd_pallas(x: jax.Array, u_bank: jax.Array,
                                     ids: jax.Array, g: jax.Array, *,
                                     block_s: int = 128,
                                     interpret: bool | None = None):
    """x/g: (B, S, d); u_bank: (A, n, db); ids: (B,).
    Returns (dx, ĝ_seq (B, n, db) f32 un-normalized partials)."""
    return _bank_bwd(x, (u_bank,), ids, g, (-2.0,), block_s, interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def etherplus_reflect_batched_bwd_pallas(x: jax.Array, u_bank: jax.Array,
                                         v_bank: jax.Array, ids: jax.Array,
                                         g: jax.Array, *,
                                         block_s: int = 128,
                                         interpret: bool | None = None):
    """Rank-2 bank reflect backward.  Returns (dx, ĝu_seq, ĝv_seq)."""
    return _bank_bwd(x, (u_bank, v_bank), ids, g, (-1.0, 1.0), block_s,
                     interpret)
