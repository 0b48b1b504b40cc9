"""Pallas TPU kernel: per-tenant bank gather + rank-2 ETHER+ reflection.

The ETHER+ analogue of ``ether_reflect_batched``: every sequence in the
batch gathers its tenant's (n, db) ``u`` AND ``v`` hyperplane vectors
from the resident ``(A, n, db)`` HBM banks (scalar-prefetch indexed DMA)
and applies the blockwise rank-2 update

    H⁺_B x = x − û(ûᵀx) + v̂(v̂ᵀx)

to that sequence's tokens.  Both projections read the *original* x (a
true rank-2 update, not two sequential reflections — see
core.transforms.etherplus_activation).  Used on the input side of a bank
GEMM and again on the output side (with the u2/v2 banks) for two-sided
ETHER+ serving — this is what makes ``--tenants N --method etherplus``
real.

Grid: (B, S/block_s); the banks ride flat as (A, 1, d) rows.  VMEM per
step ≈ 4·block_s·d·dtype + f32 temporaries (``blockwise.ROW_VMEM``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blockwise as bw
from repro.kernels.etherplus_gemm import _rank2


def _ep_reflect_batched_kernel(ids_ref, u_ref, v_ref, x_ref, o_ref, *,
                               db: int):
    del ids_ref  # consumed by the index maps, not the body
    out = _rank2(x_ref[0].astype(jnp.float32), u_ref[0], v_ref[0], db)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def etherplus_reflect_batched_pallas(x: jax.Array, u_bank: jax.Array,
                                     v_bank: jax.Array, ids: jax.Array, *,
                                     block_s: int = 128,
                                     interpret: bool | None = None
                                     ) -> jax.Array:
    """x: (B, S, d); u_bank/v_bank: (A, n, db), n*db == d; ids: (B,).

    Returns H⁺_B(ids[b]) x[b] — each sequence rank-2-reflected by its
    own tenant's hyperplane pair."""
    from repro.core.execute import _interpret, largest_divisor
    b, s, d = x.shape
    a, n, db = u_bank.shape
    assert n * db == d and u_bank.shape == v_bank.shape, (n, db, d)
    block_s = largest_divisor(s, block_s)   # odd decode shapes must work
    grid = (b, s // block_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j, ids_ref: (ids_ref[i], 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, j, ids_ref: (ids_ref[i], 0, 0)),
            pl.BlockSpec((1, block_s, d), lambda i, j, ids_ref: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_s, d),
                               lambda i, j, ids_ref: (i, j, 0)),
    )
    return pl.pallas_call(
        functools.partial(_ep_reflect_batched_kernel, db=db),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        compiler_params=bw.ROW_VMEM,
        interpret=_interpret(interpret),
    )(ids.astype(jnp.int32), u_bank.reshape(a, 1, d),
      v_bank.reshape(a, 1, d), x)
