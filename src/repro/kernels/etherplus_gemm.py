"""Pallas TPU kernel: fused rank-2 reflect-and-matmul for ETHER+.

Computes ``y = (H⁺_B x) @ W`` — and, when the adapter is two-sided,
``y = ((H⁺_B x) @ W) H̃⁺_B`` — in a single pass.  ETHER+'s blockwise
update is a *true rank-2* transform read off the original activations,

    H⁺x = x − û(ûᵀx) + v̂(v̂ᵀx),

NOT two sequential reflections (see core.transforms.etherplus_activation).
The plain-jnp formulation costs three HBM round-trips of activations per
adapted linear (reflect, GEMM, output-side reflect); here the input-side
update happens on the x-tile *inside the GEMM k-loop* (mirroring
``householder_gemm``'s Tk % db tiling) and the output-side update is a
*fused epilogue* applied to the f32 accumulator tile right before
writeback — reflected activations never exist in HBM.

Grid: (M/Tm, F/Tf, K/Tk), K innermost for f32 scratch accumulation.
Constraints:
* ``Tk % db_in == 0`` — each K-tile holds whole input reflection blocks,
  so the blockwise projections are tile-local;
* two-sided only: ``Tf % db_out == 0`` — the epilogue reflects the
  accumulator on the *output* feature dim, so each F-tile must hold
  whole output blocks (otherwise a block's projection v̂ᵀy would span
  two grid steps).  ops.py enforces these and falls back to the jnp ref.
VMEM per step ≈ (Tm·Tk + Tk·Tf + 2·Tm·Tf)·4B + adapter vectors (KBs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw


def _rank2(x, u, v, db):
    """x: (T, K) f32; u, v: (1, K) raw. Blockwise x − û(ûᵀx) + v̂(v̂ᵀx)."""
    e = bw.block_matrix(x.shape[1], db)
    un = bw.unit(u.astype(jnp.float32), e)
    vn = bw.unit(v.astype(jnp.float32), e)
    return bw.update(x, [(un, -1.0), (vn, 1.0)], e)


def _ep_body(u1_ref, v1_ref, x_ref, w_ref, acc_ref, *, db: int):
    """Shared k-step: rank-2 reflect the x-tile, accumulate the GEMM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                           # (Tm, Tk)
    xr = _rank2(x.astype(jnp.float32), u1_ref[...], v1_ref[...], db)
    acc_ref[...] += bw.matmul(xr.astype(x.dtype), w_ref[...].astype(x.dtype),
                              ((1,), (0,)))


def _ep_gemm_kernel(u1_ref, v1_ref, x_ref, w_ref, o_ref, acc_ref, *,
                    db: int):
    _ep_body(u1_ref, v1_ref, x_ref, w_ref, acc_ref, db=db)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ep_gemm_kernel_2s(u1_ref, v1_ref, u2_ref, v2_ref, x_ref, w_ref, o_ref,
                       acc_ref, *, db: int, db_out: int):
    _ep_body(u1_ref, v1_ref, x_ref, w_ref, acc_ref, db=db)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        y = _rank2(acc_ref[...], u2_ref[...], v2_ref[...], db_out)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_f", "block_k",
                                    "interpret"))
def etherplus_gemm_pallas(x: jax.Array, w: jax.Array, u1: jax.Array,
                          v1: jax.Array, u2: jax.Array | None = None,
                          v2: jax.Array | None = None, *,
                          block_m: int = 128, block_f: int = 128,
                          block_k: int = 512,
                          interpret: bool | None = None) -> jax.Array:
    """x: (T, d); w: (d, f); u1/v1: (n, db).  Two-sided when u2/v2
    (n_out, db_out) are given: the H̃⁺ epilogue reflects the accumulator
    on the output blocks before writeback.

    interpret=None auto-detects via core.execute._interpret."""
    from repro.core.execute import _interpret, largest_divisor
    interpret = _interpret(interpret)
    t, d = x.shape
    d2, f = w.shape
    n, db = u1.shape
    assert d == d2 and n * db == d and u1.shape == v1.shape
    # largest divisor of t (odd decode shapes must not crash; see
    # ether_reflect_pallas — same guard)
    block_m = largest_divisor(t, block_m)
    block_f = largest_divisor(f, block_f)
    if u2 is not None:
        # two-sided epilogue needs whole output blocks per F-tile:
        # shrink further until block_f is a multiple of db_out too
        # (terminates at db_out, which divides f by construction).
        db_out = u2.shape[1]
        while f % block_f or block_f % db_out:
            block_f -= 1
    block_k = min(block_k, d)
    if block_k % db:
        block_k = db * max(1, block_k // db)
    assert d % block_k == 0, "caller guarantees whole K-blocks (ops.py)"
    grid = (t // block_m, f // block_f, d // block_k)
    in_spec = pl.BlockSpec((1, block_k), lambda i, j, k: (0, k))
    if u2 is None:
        kernel = functools.partial(_ep_gemm_kernel, db=db)
        adapter_specs = [in_spec, in_spec]
        adapter_args = (u1.reshape(1, d), v1.reshape(1, d))
    else:
        n_out, db_out = u2.shape
        assert n_out * db_out == f and u2.shape == v2.shape
        kernel = functools.partial(_ep_gemm_kernel_2s, db=db, db_out=db_out)
        out_spec = pl.BlockSpec((1, block_f), lambda i, j, k: (0, j))
        adapter_specs = [in_spec, in_spec, out_spec, out_spec]
        adapter_args = (u1.reshape(1, d), v1.reshape(1, d),
                        u2.reshape(1, f), v2.reshape(1, f))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=adapter_specs + [
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_f), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_f), jnp.float32)],
        interpret=interpret,
    )(*adapter_args, x, w)
