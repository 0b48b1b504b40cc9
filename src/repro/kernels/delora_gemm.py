"""Pallas TPU kernels: fused DeLoRA dense ``y = xW + ((x a)·s) b``.

DeLoRA (decoupled angle/strength low-rank, arXiv 2503.18225) applies a
LoRA-shaped update whose per-component scale ``s_j = (λ/r)/(‖a_j‖‖b_j‖)``
is computed *outside* the kernel (methods.DeLoRAMethod.scale) and passed
as a primal — so the kernel is a plain fused GEMM-plus-thin-GEMM and its
hand-derived backward never touches the ε-norm chain.

The plain-jnp formulation costs two HBM round-trips of activations per
adapted linear (base GEMM, then the rank-r correction).  Here the thin
projection ``h = x @ a`` accumulates in a second f32 VMEM scratch inside
the base GEMM's k-loop, and the rank-r correction ``(h·s) @ b`` is a
fused epilogue on the accumulator right before writeback — the (T, r)
intermediate never exists in HBM.

Grid: (M/Tm, F/Tf, K/Tk), K innermost for f32 scratch accumulation.
Accepted redundancy: the ``x @ a`` accumulation reruns once per F-tile
(r ≪ f, so the extra FLOPs are ~r/Tf of the base GEMM).  The rank axis
r is kept whole per tile — adapters are KBs, like the (n, db) hyperplane
banks in householder_gemm.

The batched bank variant adds a leading (B,) grid axis with
scalar-prefetch tenant-id gathers (see householder_gemm_batched): each
sequence pulls its own (d, r)/(r, f)/(r,) adapter rows on the fly, so a
mixed-tenant batch runs in one kernel launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _delora_kernel(s_ref, a_ref, b_ref, x_ref, w_ref, o_ref, acc_ref,
                   h_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)                       # (Tm, Tk)
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    h_ref[...] += jax.lax.dot_general(
        x, a_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        hs = h_ref[...] * s_ref[...].astype(jnp.float32)     # (Tm, r)·(1, r)
        y = acc_ref[...] + jax.lax.dot_general(
            hs, b_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_f",
                                             "block_k", "interpret"))
def delora_gemm_pallas(x: jax.Array, w: jax.Array, a: jax.Array,
                       b: jax.Array, s: jax.Array, *, block_m: int = 128,
                       block_f: int = 128, block_k: int = 512,
                       interpret: bool | None = None) -> jax.Array:
    """x: (T, d); w: (d, f); a: (d, r); b: (r, f); s: (r,) pre-normalized
    scale (see module docstring).  interpret=None auto-detects."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    t, d = x.shape
    d2, f = w.shape
    da, r = a.shape
    assert d == d2 and da == d and b.shape == (r, f) and s.shape == (r,)
    block_m = largest_divisor(t, block_m)
    block_f = largest_divisor(f, block_f)
    block_k = largest_divisor(d, min(block_k, d))
    grid = (t // block_m, f // block_f, d // block_k)
    return pl.pallas_call(
        _delora_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, r), lambda i, j, k: (0, 0)),           # s
            pl.BlockSpec((block_k, r), lambda i, j, k: (k, 0)),     # a
            pl.BlockSpec((r, block_f), lambda i, j, k: (0, j)),     # b
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_f), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_f), jnp.float32),
                        pltpu.VMEM((block_m, r), jnp.float32)],
        interpret=interpret,
    )(s.reshape(1, r), a, b, x, w)


def _delora_batched_kernel(ids_ref, s_ref, a_ref, b_ref, x_ref, w_ref,
                           o_ref, acc_ref, h_ref):
    del ids_ref  # consumed by the index maps
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)                         # (Ts, Tk)
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    h_ref[...] += jax.lax.dot_general(
        x, a_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(3) - 1)
    def _done():
        hs = h_ref[...] * s_ref[0].astype(jnp.float32)
        y = acc_ref[...] + jax.lax.dot_general(
            hs, b_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "block_f",
                                             "block_k", "interpret"))
def delora_gemm_batched_pallas(x: jax.Array, w: jax.Array,
                               a_bank: jax.Array, b_bank: jax.Array,
                               s_bank: jax.Array, ids: jax.Array, *,
                               block_s: int = 128, block_f: int = 128,
                               block_k: int = 512,
                               interpret: bool | None = None) -> jax.Array:
    """x: (B, S, d); w: (d, f); a_bank: (A, d, r); b_bank: (A, r, f);
    s_bank: (A, r); ids: (B,) int — each sequence's adapter rows are
    gathered by the scalar-prefetch index maps."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    bsz, seq, d = x.shape
    d2, f = w.shape
    na, da, r = a_bank.shape
    assert d == d2 and da == d and b_bank.shape == (na, r, f)
    assert s_bank.shape == (na, r) and ids.shape == (bsz,)
    block_s = largest_divisor(seq, block_s)
    block_f = largest_divisor(f, block_f)
    block_k = largest_divisor(d, min(block_k, d))
    grid = (bsz, seq // block_s, f // block_f, d // block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, r),
                         lambda i, j, jf, k, ids_ref: (ids_ref[i], 0, 0)),
            pl.BlockSpec((1, block_k, r),
                         lambda i, j, jf, k, ids_ref: (ids_ref[i], k, 0)),
            pl.BlockSpec((1, r, block_f),
                         lambda i, j, jf, k, ids_ref: (ids_ref[i], 0, jf)),
            pl.BlockSpec((1, block_s, block_k),
                         lambda i, j, jf, k, ids_ref: (i, j, k)),
            pl.BlockSpec((block_k, block_f),
                         lambda i, j, jf, k, ids_ref: (k, jf)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_f),
                               lambda i, j, jf, k, ids_ref: (i, j, jf)),
        scratch_shapes=[pltpu.VMEM((block_s, block_f), jnp.float32),
                        pltpu.VMEM((block_s, r), jnp.float32)],
    )
    return pl.pallas_call(
        _delora_batched_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, seq, f), x.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), s_bank.reshape(na, 1, r), a_bank, b_bank, x, w)
