"""Pallas TPU kernel: per-tenant bank gather-and-reflect (multi-tenant).

The serving hot op of DESIGN.md §2: every sequence in the batch carries a
tenant id; its hyperplane vectors are gathered from the resident
``(num_adapters, n, db)`` HBM bank and the block-diagonal Householder
reflection ``H_B x = x − 2û(ûᵀx)`` is applied to that sequence's tokens.
This is the batched analogue of ``ether_reflect`` — and the reason ETHER
can serve thousands of tenants from one weight set: the bank is a few MB
(O(A·d) floats), the gather is free (scalar-prefetch indexed DMA — the
id picks the bank *row* that is staged into VMEM), and the frozen-GEMM
that follows is tenant-independent.

Grid: (B, S/block_s).  The tenant ids ride in scalar-prefetch memory so
the BlockSpec index map can address the bank by id before the kernel
body runs; each grid step stages one flat (1, 1, d) bank row and one
(1, block_s, d) token tile.  VMEM per step ≈ 4·block_s·d·dtype + f32
temporaries (``blockwise.ROW_VMEM``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw


def _reflect_batched_kernel(ids_ref, u_ref, x_ref, o_ref, *, db: int):
    del ids_ref  # consumed by the index maps, not the body
    e = bw.block_matrix(x_ref.shape[2], db)
    un = bw.unit(u_ref[0].astype(jnp.float32), e)            # (1, d)
    out = bw.update(x_ref[0].astype(jnp.float32), [(un, -2.0)], e)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def ether_reflect_batched_pallas(x: jax.Array, u_bank: jax.Array,
                                 ids: jax.Array, *, block_s: int = 128,
                                 interpret: bool | None = None
                                 ) -> jax.Array:
    """x: (B, S, d); u_bank: (A, n, db) with n*db == d; ids: (B,) int32.

    Returns H_B(ids[b]) x[b] — each sequence reflected by its own
    tenant's hyperplanes.
    """
    from repro.core.execute import _interpret
    b, s, d = x.shape
    a, n, db = u_bank.shape
    assert n * db == d, (n, db, d)
    block_s = min(block_s, s)
    assert s % block_s == 0, "caller pads tokens to a multiple of block_s"
    grid = (b, s // block_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # the tenant id selects the bank row staged into VMEM
            pl.BlockSpec((1, 1, d), lambda i, j, ids_ref: (ids_ref[i], 0, 0)),
            pl.BlockSpec((1, block_s, d), lambda i, j, ids_ref: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_s, d),
                               lambda i, j, ids_ref: (i, j, 0)),
    )
    return pl.pallas_call(
        functools.partial(_reflect_batched_kernel, db=db),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        compiler_params=bw.ROW_VMEM,
        interpret=_interpret(interpret),
    )(ids.astype(jnp.int32), u_bank.reshape(a, 1, d), x)
