"""Pallas TPU kernel: fused reflect-and-matmul ``y = (H_B W)ᵀ x``.

The TPU-native fusion of the paper's §3.4 block-parallel scheme: instead
of materializing the transformed weight (O(d·f) extra HBM traffic per
step, or O(d²f/n) FLOPs in the paper's literal block-GEMM form), the
Householder reflection is applied to the x-tile *inside the GEMM k-loop*,
so transformed weights never exist anywhere — not in HBM, not in VMEM.

Grid: (M/Tm, F/Tf, K/Tk), K innermost for f32 scratch accumulation.
Constraint: Tk % db == 0 (each K-tile holds whole reflection blocks, so
the blockwise projection is tile-local). ops.py enforces/falls back.
The adapter rides flat as a (1, Tk) row (kernels/blockwise.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw


def _hh_gemm_kernel(u_ref, x_ref, w_ref, o_ref, acc_ref, *, db: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                           # (Tm, Tk)
    e = bw.block_matrix(x.shape[1], db)
    un = bw.unit(u_ref[...].astype(jnp.float32), e)          # (1, Tk)
    xr = bw.update(x.astype(jnp.float32), [(un, -2.0)], e)
    acc_ref[...] += bw.matmul(xr.astype(x.dtype), w_ref[...].astype(x.dtype),
                              ((1,), (0,)))

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_f", "block_k",
                                    "interpret"))
def householder_gemm_pallas(x: jax.Array, w: jax.Array, u: jax.Array, *,
                            block_m: int = 128, block_f: int = 128,
                            block_k: int = 512,
                            interpret: bool | None = None) -> jax.Array:
    """x: (T, d); w: (d, f); u: (n, db). Returns reflect(x) @ w.

    interpret=None auto-detects via core.execute._interpret."""
    from repro.core.execute import _interpret
    interpret = _interpret(interpret)
    t, d = x.shape
    d2, f = w.shape
    n, db = u.shape
    assert d == d2 and n * db == d
    block_m = min(block_m, t)
    block_f = min(block_f, f)
    block_k = min(block_k, d)
    # whole blocks per K-tile
    if block_k % db:
        block_k = db * max(1, block_k // db)
    assert t % block_m == 0 and f % block_f == 0 and d % block_k == 0
    grid = (t // block_m, f // block_f, d // block_k)
    return pl.pallas_call(
        functools.partial(_hh_gemm_kernel, db=db),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_k), lambda i, j, k: (0, k)),
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_f), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_f), jnp.float32)],
        interpret=interpret,
    )(u.reshape(1, d), x, w)
