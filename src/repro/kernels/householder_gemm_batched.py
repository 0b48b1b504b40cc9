"""Pallas TPU kernel: fused tenant-gather + reflect + GEMM (multi-tenant).

The bank-serving analogue of ``householder_gemm``: every sequence in the
batch carries a tenant id whose hyperplane vectors are gathered from the
resident ``(A, n, db)`` HBM bank via scalar-prefetch indexing, the
block-diagonal Householder reflection ``H_B x = x − 2û(ûᵀx)`` is
applied to the x-tile *inside the GEMM k-loop*, and the result feeds the
shared frozen-weight GEMM — so bank serving no longer materializes
reflected activations in HBM (previously: ``ether_reflect_batched``
wrote H_B x back to HBM and a separate XLA GEMM re-read it).

Grid: (B, S/Ts, F/Tf, K/Tk), K innermost for f32 scratch accumulation.
The tenant ids ride in scalar-prefetch SMEM; the bank is viewed flat as
``(A, 1, d)`` and its BlockSpec's index map addresses the id'd row's
(1, Tk) slice for the current K-tile, so the gather is a free indexed
DMA of a lane-aligned block.  Constraint: Tk % db == 0 (whole
reflection blocks per K-tile).  VMEM per step ≈ (Ts·Tk + Tk·Tf)·2·dtype
+ Ts·Tf·4B.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw


def _hh_gemm_batched_kernel(ids_ref, u_ref, x_ref, w_ref, o_ref, acc_ref, *,
                            db: int):
    del ids_ref  # consumed by the index maps, not the body
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                             # (Ts, Tk)
    e = bw.block_matrix(x.shape[1], db)
    un = bw.unit(u_ref[0].astype(jnp.float32), e)            # (1, Tk)
    xr = bw.update(x.astype(jnp.float32), [(un, -2.0)], e)
    acc_ref[...] += bw.matmul(xr.astype(x.dtype), w_ref[...].astype(x.dtype),
                              ((1,), (0,)))

    @pl.when(k == pl.num_programs(3) - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_f", "block_k",
                                    "interpret"))
def householder_gemm_batched_pallas(x: jax.Array, w: jax.Array,
                                    u_bank: jax.Array, ids: jax.Array, *,
                                    block_s: int = 128, block_f: int = 128,
                                    block_k: int = 512,
                                    interpret: bool | None = None
                                    ) -> jax.Array:
    """x: (B, S, d); w: (d, f); u_bank: (A, n, db), n*db == d; ids: (B,).

    Returns reflect(x[b], u_bank[ids[b]]) @ w for every sequence b."""
    from repro.core.execute import _interpret, largest_divisor
    b, s, d = x.shape
    d2, f = w.shape
    a, n, db = u_bank.shape
    assert d == d2 and n * db == d, (n, db, d)
    block_s = largest_divisor(s, block_s)   # odd decode shapes must work
    block_f = largest_divisor(f, block_f)
    block_k = min(block_k, d)
    if block_k % db:
        block_k = db * max(1, block_k // db)
    assert d % block_k == 0, "caller guarantees whole K-blocks (ops.py)"
    grid = (b, s // block_s, f // block_f, d // block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # the tenant id selects the bank row's slice for this K-tile
            pl.BlockSpec((1, 1, block_k),
                         lambda i, j, jf, k, ids_ref: (ids_ref[i], 0, k)),
            pl.BlockSpec((1, block_s, block_k),
                         lambda i, j, jf, k, ids_ref: (i, j, k)),
            pl.BlockSpec((block_k, block_f),
                         lambda i, j, jf, k, ids_ref: (k, jf)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_f),
                               lambda i, j, jf, k, ids_ref: (i, j, jf)),
        scratch_shapes=[pltpu.VMEM((block_s, block_f), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_hh_gemm_batched_kernel, db=db),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, f), x.dtype),
        interpret=_interpret(interpret),
    )(ids.astype(jnp.int32), u_bank.reshape(a, 1, d), x, w)
