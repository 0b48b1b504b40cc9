"""Blockwise reflection math on flat 2-D tiles, shared by every
reflection kernel.

A reflection adapter ``u: (n, db)`` acts on n contiguous blocks of db
features.  Inside a kernel the data tile stays 2-D — rows × K features —
and the adapter rides as one ``(1, K)`` row over the same K features
(the wrappers pass ``u.reshape(1, d)``).  Per-block sums are MXU
products with the 0/1 block matrix ``E: (K, K/db)``, ``E[c, j] = 1`` iff
feature c lies in block j: ``block_sum(v) = v @ E`` and
``block_bcast(p) = p @ Eᵀ``.

Why not ``x.reshape(rows, n, db)`` and an einsum: Mosaic has no lowering
for the batched ``"tnb,nb->tn"`` contraction, and a reshape that splits
the lane dim into db-wide pieces is refused for db < 128 (Phi-1.5's
d=2048 at n=32 gives db=64).  The block-matrix products compile for any
db that divides K and run on the MXU at ``HIGHEST`` precision, so the
projections keep f32 accuracy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-8


def block_matrix(k: int, db: int) -> jax.Array:
    """(k, k // db) f32 0/1 matrix: column j marks block j's features."""
    c = jax.lax.broadcasted_iota(jnp.int32, (k, k // db), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (k, k // db), 1)
    return ((c >= j * db) & (c < (j + 1) * db)).astype(jnp.float32)


def block_sum(v: jax.Array, e: jax.Array) -> jax.Array:
    """(r, K) → (r, K/db): the sum over each block's features."""
    return jax.lax.dot_general(v, e, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def block_bcast(p: jax.Array, e: jax.Array) -> jax.Array:
    """(r, K/db) → (r, K): each block's value on all of its features."""
    return jax.lax.dot_general(p, e, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def project(x: jax.Array, un: jax.Array, e: jax.Array) -> jax.Array:
    """Blockwise ûᵀx, broadcast back over each block: (r, K) f32."""
    return block_bcast(block_sum(x * un, e), e)


def unit(u: jax.Array, e: jax.Array) -> jax.Array:
    """Raw (1, K) adapter row → û, every block scaled to unit length."""
    return u / (jnp.sqrt(project(u, u, e)) + EPS)


def update(x: jax.Array, dirs, e: jax.Array) -> jax.Array:
    """x + Σ coeff·û(ûᵀx) over ``dirs = [(û, coeff), ...]``; every
    projection reads the original x (ETHER+ is a true rank-2 update)."""
    out = x
    for un, coeff in dirs:
        out = out + coeff * project(x, un, e) * un
    return out


def norm_chain(u: jax.Array, ghat: jax.Array, e: jax.Array) -> jax.Array:
    """Pull dL/dû back through û = u/(‖u‖+ε) per block; (1, K) f32.
    The flat-layout twin of ``reflect_bwd.norm_chain``."""
    r = jnp.sqrt(project(u, u, e))
    s = r + EPS
    return ghat / s - project(u, ghat, e) * u / (r * s * s)


def update_bwd(x: jax.Array, g: jax.Array, dirs, e: jax.Array):
    """Backward of :func:`update` under cotangent g (both (r, K) f32).

    The operator is symmetric, so dx = update(g); each direction's
    un-normalized dL/dû is coeff·Σ_rows[(ûᵀx) g + (ûᵀg) x], (1, K)."""
    dx = g
    ghats = []
    for un, coeff in dirs:
        px, pg = project(x, un, e), project(g, un, e)
        dx = dx + coeff * pg * un
        ghats.append(coeff * jnp.sum(px * g + pg * x, axis=0,
                                     keepdims=True))
    return dx, ghats


def matmul(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """MXU product with f32 accumulation in the operands' common dtype
    (bf16 weights stay single-pass bf16; f32 operands stay f32)."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(a.astype(dt), b.astype(dt), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# Whole-row kernels (ether_reflect*, the reflect backwards) hold
# (rows, d) tiles plus their f32 temporaries: 256 rows at d=8192 need
# ~60 MB, past the 16 MB default scoped VMEM of a v5e TensorCore
# (128 MiB physical).
ROW_VMEM = pltpu.CompilerParams(vmem_limit_bytes=100 * 2**20)
