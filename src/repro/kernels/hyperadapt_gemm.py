"""Pallas TPU kernels: fused HyperAdapt dense ``y = ((x·r) W)·c``.

HyperAdapt (arXiv 2509.18629) finetunes a per-input-feature scale
``r: (d,)`` and a per-output-feature scale ``c: (f,)`` — the adapted
weight is ``diag(r) W diag(c)`` but is never materialized: the row
scale multiplies the x-tile inside the GEMM k-loop (free on the VPU
while the MXU runs) and the column scale is a fused epilogue on the f32
accumulator right before writeback.

Grid: (M/Tm, F/Tf, K/Tk), K innermost for f32 scratch accumulation —
the same shape as householder_gemm with the blockwise reflection
replaced by elementwise scales.  The wrapper passes r/c as (1, d)/(1, f)
so tiles stay 2-D (TPU-friendly lane layout).

The batched bank variant adds a leading (B,) grid axis with
scalar-prefetch tenant-id gathers: r_bank (A, d) / c_bank (A, f) rows
are pulled per sequence, so a mixed-tenant batch is one kernel launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ha_kernel(r_ref, c_ref, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                       # (Tm, Tk)
    xr = x * r_ref[...].astype(jnp.float32)                  # row scale
    acc_ref[...] += jax.lax.dot_general(
        xr, w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_ref[...]
                      * c_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_f",
                                             "block_k", "interpret"))
def hyperadapt_gemm_pallas(x: jax.Array, w: jax.Array, r: jax.Array,
                           c: jax.Array, *, block_m: int = 128,
                           block_f: int = 128, block_k: int = 512,
                           interpret: bool | None = None) -> jax.Array:
    """x: (T, d); w: (d, f); r: (d,); c: (f,).  interpret=None
    auto-detects via core.execute._interpret."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    t, d = x.shape
    d2, f = w.shape
    assert d == d2 and r.shape == (d,) and c.shape == (f,)
    block_m = largest_divisor(t, block_m)
    block_f = largest_divisor(f, block_f)
    block_k = largest_divisor(d, min(block_k, d))
    grid = (t // block_m, f // block_f, d // block_k)
    return pl.pallas_call(
        _ha_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_k), lambda i, j, k: (0, k)),     # r
            pl.BlockSpec((1, block_f), lambda i, j, k: (0, j)),     # c
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_f), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_f), jnp.float32)],
        interpret=interpret,
    )(r.reshape(1, d), c.reshape(1, f), x, w)


def _ha_batched_kernel(ids_ref, r_ref, c_ref, x_ref, w_ref, o_ref,
                       acc_ref):
    del ids_ref  # consumed by the index maps
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)                         # (Ts, Tk)
    xr = x * r_ref[0].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        xr, w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(3) - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    * c_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "block_f",
                                             "block_k", "interpret"))
def hyperadapt_gemm_batched_pallas(x: jax.Array, w: jax.Array,
                                   r_bank: jax.Array, c_bank: jax.Array,
                                   ids: jax.Array, *, block_s: int = 128,
                                   block_f: int = 128, block_k: int = 512,
                                   interpret: bool | None = None
                                   ) -> jax.Array:
    """x: (B, S, d); w: (d, f); r_bank: (A, d); c_bank: (A, f);
    ids: (B,) int — per-sequence scale rows via scalar prefetch."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    bsz, seq, d = x.shape
    d2, f = w.shape
    na = r_bank.shape[0]
    assert d == d2 and r_bank.shape == (na, d) and c_bank.shape == (na, f)
    assert ids.shape == (bsz,)
    block_s = largest_divisor(seq, block_s)
    block_f = largest_divisor(f, block_f)
    block_k = largest_divisor(d, min(block_k, d))
    grid = (bsz, seq // block_s, f // block_f, d // block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_k),
                         lambda i, j, jf, k, ids_ref: (ids_ref[i], 0, k)),
            pl.BlockSpec((1, 1, block_f),
                         lambda i, j, jf, k, ids_ref: (ids_ref[i], 0, jf)),
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
        _ha_batched_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, seq, f), x.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), r_bank.reshape(na, 1, d),
      c_bank.reshape(na, 1, f), x, w)
