"""Pallas TPU kernel: fused tenant-gather + reflect + GEMM (multi-tenant).

The bank-serving analogue of ``householder_gemm``: every sequence in the
batch carries a tenant id whose hyperplane vectors are gathered from the
resident ``(A, n, db)`` HBM bank, the block-diagonal Householder
reflection ``H_B x = x − 2û(ûᵀx)`` is applied to the x-tile inside the
kernel, and the result feeds the shared frozen-weight GEMM — so bank
serving never materializes reflected activations in HBM.  One entry
point, two grids, picked from the input's shape:

Per-sequence grid (S > 1, prefill and training): (B, S/Ts, F/Tf, K/Tk),
K innermost for f32 scratch accumulation.  The tenant ids ride in
scalar-prefetch SMEM; the bank is viewed flat as ``(A, 1, d)`` and its
BlockSpec's index map addresses the id'd row's (1, Tk) slice for the
current K-tile, so the gather is a free indexed DMA of a lane-aligned
block.  Each W tile is read once per sequence and row tile.  VMEM per
step ≈ (Ts·Tk + Tk·Tf)·2·dtype + Ts·Tf·4B.

Folded grid (S == 1, the decode step): (F/Tf, K/Tk), K innermost.  The
B one-row sequences form one (B, Tk) x tile, so each W tile is read
once per call for all slots, not once per sequence.  The wrapper
gathers the B bank rows (B·d·4 bytes, against d·f·2 of W); each K-tile
of the rows is reflected once, at the first F-tile, into a
``(K/Tk, B, Tk)`` VMEM scratch that the later F-tiles read (so the grid
runs in order).  W is pinned to HBM and streams through the kernel's
pipeline in tiles of whole rows (Tf = f up to 8192), each one contiguous
DMA of about 1 MB.  VMEM per step ≈
2·Tk·Tf·dtype (W, double-buffered) + B·d·dtype (reflected rows) +
B·Tf·4B (accumulator) + small (B, Tk) tiles: at Phi-1.5's decode
shapes (B = 8; Tk×Tf 256×2048, 128×8192, 256×2048) 2.2–4.5 MB.

Both grids keep the same math: f32 blockwise reflection through
``kernels/blockwise.py``, MXU operands in x's dtype, f32 accumulation.
Constraint: Tk % db == 0 (whole reflection blocks per K-tile).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw


# Folded-grid tiles, chosen by timing on a TPU v5e (PERF.md §6): whole W
# rows up to FOLD_BLOCK_F wide, so each W tile is one contiguous DMA, and
# about FOLD_TILE_BYTES of W per grid step (smaller tiles pay more in
# per-step overhead, larger ones in the unhidden first DMA of each call).
FOLD_BLOCK_F, FOLD_TILE_BYTES = 8192, 2**20
_FOLD_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"),   # F-tile 0 fills xr
    vmem_limit_bytes=64 * 2**20)


def fold_tiles(d: int, f: int, db: int, block_f: int, block_k: int,
               itemsize: int) -> tuple[int, int]:
    """(block_f, block_k) of the folded grid, from the legal
    per-sequence tiles (``execute.gemm_tiles``): Tf whole rows up to the
    cap, Tk in whole reflection blocks, 128-lane aligned where d allows
    (else the per-sequence K tile), dividing d."""
    from repro.core.execute import largest_divisor
    tf = block_f * largest_divisor(f // block_f,
                                   max(1, FOLD_BLOCK_F // block_f))
    unit = math.lcm(db, 128)
    if d % unit:
        unit = block_k
    per_tile = max(1, FOLD_TILE_BYTES // (itemsize * tf * unit))
    return tf, unit * largest_divisor(d // unit, per_tile)


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


def _hh_gemm_batched_kernel_rows(x_ref, u_ref, w_ref, o_ref, xr_ref, acc_ref,
                                 *, db: int):
    """Folded grid: one (B, Tk) tile holds every sequence's row."""
    jf, k = pl.program_id(0), pl.program_id(1)

    @pl.when(jf == 0)
    def _reflect():                  # each K-tile of the rows, once a call
        x = x_ref[...]                                       # (B, Tk)
        e = bw.block_matrix(x.shape[1], db)
        un = bw.unit(u_ref[...].astype(jnp.float32), e)      # a û per row
        xr = bw.update(x.astype(jnp.float32), [(un, -2.0)], e)
        xr_ref[k] = xr.astype(xr_ref.dtype)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += bw.matmul(xr_ref[k], w_ref[...].astype(xr_ref.dtype),
                              ((1,), (0,)))

    @pl.when(k == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _folded(x, w, u_bank, ids, block_f, block_k, db, interpret):
    b, _, d = x.shape
    f = w.shape[1]
    nk = d // block_k
    u = u_bank[ids].reshape(b, d)          # gather, then flatten B rows only

    def rows(jf, k):
        # read on F-tile 0 only; later F-tiles keep the last block (no DMA)
        return 0, jnp.where(jf == 0, k, nk - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(f // block_f, nk),
        in_specs=[
            pl.BlockSpec((b, block_k), rows),
            pl.BlockSpec((b, block_k), rows),
            pl.BlockSpec((block_k, block_f), lambda jf, k: (k, jf)),
        ],
        out_specs=pl.BlockSpec((b, block_f), lambda jf, k: (0, jf)),
        scratch_shapes=[pltpu.VMEM((nk, b, block_k), x.dtype),
                        pltpu.VMEM((b, block_f), jnp.float32)],
    )
    if not interpret:
        # W stays in HBM and streams through the kernel's pipeline: left
        # free, XLA stages a layer-scan slice of it in VMEM with a copy
        # of its own, outside the kernel
        w = pltpu.with_memory_space_constraint(w, pltpu.HBM)
    out = pl.pallas_call(
        functools.partial(_hh_gemm_batched_kernel_rows, db=db),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, f), x.dtype),
        compiler_params=_FOLD_PARAMS,
        interpret=interpret,
    )(x.reshape(b, d), u, w)
    return out.reshape(b, 1, f)


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

    Returns reflect(x[b], u_bank[ids[b]]) @ w for every sequence b.
    S == 1 takes the folded grid (``block_s`` unused), S > 1 the
    per-sequence one."""
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
    if s == 1:
        return _folded(x, w, u_bank, ids, block_f, block_k, db,
                       _interpret(interpret))
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
