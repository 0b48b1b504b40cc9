"""Jit'd public wrappers for the Pallas kernels.

Every wrapper auto-selects interpret mode (Python emulation) off-TPU so
the identical kernel code is validated on CPU and deployed on TPU, and
falls back to the pure-jnp reference for shapes the kernel's tiling
constraints reject (odd remainders); the tests sweep both paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.execute import (_interpret, gemm_tiles, lane_ok,
                                largest_divisor)
from repro.kernels import ref
from repro.kernels.delora_gemm import (delora_gemm_pallas,
                                       delora_gemm_batched_pallas)
from repro.kernels.ether_reflect import ether_reflect_pallas
from repro.kernels.ether_reflect_batched import ether_reflect_batched_pallas
from repro.kernels.ether_merge import ether_merge_pallas
from repro.kernels.etherplus_gemm import etherplus_gemm_pallas
from repro.kernels.etherplus_merge import (etherplus_merge_left_pallas,
                                           etherplus_merge_right_pallas)
from repro.kernels.etherplus_reflect_batched import (
    etherplus_reflect_batched_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gemm_bwd import (householder_gemm_batched_bwd_pallas,
                                    householder_gemm_batched_dw_pallas,
                                    reflect_gemm_dx_pallas,
                                    reflect_gemm_dw_pallas)
from repro.kernels.householder_gemm import householder_gemm_pallas
from repro.kernels.householder_gemm_batched import (
    householder_gemm_batched_pallas)
from repro.kernels.hyperadapt_gemm import (hyperadapt_gemm_pallas,
                                           hyperadapt_gemm_batched_pallas)
from repro.kernels.method_merge import (delora_merge_pallas,
                                        hyperadapt_merge_pallas)
from repro.kernels.merge_bwd import (merge_left_bwd_pallas,
                                     merge_right_bwd_pallas)
from repro.kernels.reflect_bwd import (ether_reflect_bwd_pallas,
                                       etherplus_reflect_bwd_pallas,
                                       norm_chain)
from repro.kernels.reflect_bwd_batched import (
    ether_reflect_batched_bwd_pallas, etherplus_reflect_batched_bwd_pallas)


def ether_reflect(x: jax.Array, u: jax.Array, *, block_t: int = 256,
                  interpret: bool | None = None) -> jax.Array:
    """H_B x over the last dim; x may have any leading dims."""
    import math
    d = x.shape[-1]
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    x2 = x.reshape(t, d)
    bt = min(block_t, t)
    if t % bt:
        return ref.ref_ether_reflect(x2, u).reshape(x.shape)
    out = ether_reflect_pallas(x2, u, block_t=bt,
                               interpret=_interpret(interpret))
    return out.reshape(x.shape)


def ether_reflect_batched(x: jax.Array, u_bank: jax.Array, ids: jax.Array,
                          *, block_s: int = 128,
                          interpret: bool | None = None) -> jax.Array:
    """Per-tenant gather-and-reflect. x: (B, S, d); u_bank: (A, n, db);
    ids: (B,). Falls back to the jnp ref for non-tileable shapes."""
    b, s, d = x.shape
    _, n, db = u_bank.shape
    bs = min(block_s, s)
    if bs == 0 or s % bs or n * db != d:
        return ref.ref_ether_reflect_batched(x, u_bank, ids)
    return ether_reflect_batched_pallas(x, u_bank, ids, block_s=bs,
                                        interpret=interpret)


def householder_gemm(x: jax.Array, w: jax.Array, u: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """reflect(x) @ w; x: (..., d); w: (d, f)."""
    import math
    from repro.core import execute
    d, f = w.shape
    lead = x.shape[:-1]
    t = math.prod(lead) if lead else 1
    x2 = x.reshape(t, d)
    n, db = u.shape
    if not execute.supports("householder_gemm", x, w, u):
        return ref.ref_householder_gemm(x2, w, u).reshape(*lead, f)
    bm, bf, bk = gemm_tiles(t, d, f, db)
    out = householder_gemm_pallas(x2, w, u, block_m=bm, block_f=bf,
                                  block_k=bk,
                                  interpret=_interpret(interpret))
    return out.reshape(*lead, f)


def etherplus_gemm(x: jax.Array, w: jax.Array, u1: jax.Array,
                   v1: jax.Array, u2: jax.Array | None = None,
                   v2: jax.Array | None = None, *,
                   interpret: bool | None = None) -> jax.Array:
    """Fused rank-2 ETHER+ linear: (H⁺x) @ w, with the two-sided H̃⁺
    epilogue when u2/v2 are given.  x: (..., d); w: (d, f)."""
    import math
    d, f = w.shape
    lead = x.shape[:-1]
    t = math.prod(lead) if lead else 1
    x2 = x.reshape(t, d)
    n, db = u1.shape
    db_out = u2.shape[1] if u2 is not None else None
    bm, bf, bk = gemm_tiles(t, d, f, db, db_out)
    if n * db != d or not (bm and bf and bk):
        return ref.ref_etherplus_gemm(x2, w, u1, v1, u2, v2
                                      ).reshape(*lead, f)
    out = etherplus_gemm_pallas(x2, w, u1, v1, u2, v2, block_m=bm,
                                block_f=bf, block_k=bk,
                                interpret=_interpret(interpret))
    return out.reshape(*lead, f)


def householder_gemm_batched(x: jax.Array, w: jax.Array,
                             u_bank: jax.Array, ids: jax.Array, *,
                             interpret: bool | None = None) -> jax.Array:
    """Fused tenant-gather + reflect + GEMM. x: (B, S, d); w: (d, f);
    u_bank: (A, n, db); ids: (B,). Falls back to the jnp ref for
    non-tileable shapes."""
    _, s, d = x.shape
    _, f = w.shape
    _, n, db = u_bank.shape
    bs, bf, bk = gemm_tiles(s, d, f, db)
    if n * db != d or not (bs and bf and bk):
        return ref.ref_householder_gemm_batched(x, w, u_bank, ids)
    return householder_gemm_batched_pallas(x, w, u_bank, ids, block_s=bs,
                                           block_f=bf, block_k=bk,
                                           interpret=interpret)


def etherplus_reflect_batched(x: jax.Array, u_bank: jax.Array,
                              v_bank: jax.Array, ids: jax.Array, *,
                              block_s: int = 128,
                              interpret: bool | None = None) -> jax.Array:
    """Per-tenant gather + rank-2 ETHER+ reflect. x: (B, S, d);
    u_bank/v_bank: (A, n, db); ids: (B,). Falls back to the jnp ref for
    non-tileable shapes."""
    _, s, d = x.shape
    _, n, db = u_bank.shape
    bs = min(block_s, s)
    if bs == 0 or s % bs or n * db != d or not lane_ok(d):
        return ref.ref_etherplus_reflect_batched(x, u_bank, v_bank, ids)
    return etherplus_reflect_batched_pallas(x, u_bank, v_bank, ids,
                                            block_s=bs, interpret=interpret)


def etherplus_merge(w: jax.Array, u1: jax.Array, v1: jax.Array,
                    u2: jax.Array | None = None,
                    v2: jax.Array | None = None, *,
                    interpret: bool | None = None) -> jax.Array:
    """ETHER+ absorption W' = H⁺_L W (H̃⁺_R when u2/v2 given). w: (d, f)."""
    from repro.core import execute
    if not execute.supports("etherplus_merge", w, u1, v1, u2, v2):
        return ref.ref_etherplus_merge(w, u1, v1, u2, v2)
    out = etherplus_merge_left_pallas(w, u1, v1,
                                      interpret=_interpret(interpret))
    if u2 is not None:
        out = etherplus_merge_right_pallas(out, u2, v2,
                                           interpret=_interpret(interpret))
    return out


def ether_merge(w: jax.Array, u: jax.Array, *,
                interpret: bool | None = None) -> jax.Array:
    """H_B w for adapter absorption. w: (d, f)."""
    from repro.core import execute
    if not execute.supports("ether_merge", w, u):
        return ref.ref_ether_merge(w, u)
    return ether_merge_pallas(w, u, block_f=_merge_block_f(w.shape[1]),
                              interpret=_interpret(interpret))


def _merge_block_f(f: int) -> int:
    """W column tile of the left merges: lane-aligned where f allows
    (always on a TPU, ``execute._sup_merge``), else whole rows."""
    return next((bf for bf in (512, 128) if f % bf == 0), f)


def delora_gemm(x: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array,
                s: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Fused DeLoRA dense xW + ((x a)·s) b.  x: (..., d); w: (d, f);
    a: (d, r); b: (r, f); s: (r,) pre-normalized scale."""
    import math
    d, f = w.shape
    lead = x.shape[:-1]
    t = math.prod(lead) if lead else 1
    x2 = x.reshape(t, d)
    bm, bf, bk = gemm_tiles(t, d, f, 1)
    if not (bm and bf and bk):
        return ref.ref_delora_gemm(x2, w, a, b, s).reshape(*lead, f)
    out = delora_gemm_pallas(x2, w, a, b, s, block_m=bm, block_f=bf,
                             block_k=bk, interpret=_interpret(interpret))
    return out.reshape(*lead, f)


def delora_gemm_batched(x: jax.Array, w: jax.Array, a_bank: jax.Array,
                        b_bank: jax.Array, s_bank: jax.Array,
                        ids: jax.Array, *,
                        interpret: bool | None = None) -> jax.Array:
    """Fused tenant-gather + DeLoRA dense.  x: (B, S, d); w: (d, f);
    a_bank: (A, d, r); b_bank: (A, r, f); s_bank: (A, r); ids: (B,)."""
    _, s, d = x.shape
    _, f = w.shape
    bs, bf, bk = gemm_tiles(s, d, f, 1)
    if not (bs and bf and bk):
        return ref.ref_delora_gemm_batched(x, w, a_bank, b_bank, s_bank,
                                           ids)
    return delora_gemm_batched_pallas(x, w, a_bank, b_bank, s_bank, ids,
                                      block_s=bs, block_f=bf, block_k=bk,
                                      interpret=interpret)


def delora_merge(w: jax.Array, a: jax.Array, b: jax.Array, s: jax.Array,
                 *, interpret: bool | None = None) -> jax.Array:
    """DeLoRA absorption W' = W + (a·s) b.  w: (d, f)."""
    f = w.shape[-1]
    if not lane_ok(f):
        return ref.ref_delora_merge(w, a, b, s)
    return delora_merge_pallas(w, a, b, s, interpret=_interpret(interpret))


def hyperadapt_gemm(x: jax.Array, w: jax.Array, r: jax.Array,
                    c: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """Fused HyperAdapt dense ((x·r) W)·c.  x: (..., d); w: (d, f)."""
    import math
    d, f = w.shape
    lead = x.shape[:-1]
    t = math.prod(lead) if lead else 1
    x2 = x.reshape(t, d)
    bm, bf, bk = gemm_tiles(t, d, f, 1)
    if not (bm and bf and bk):
        return ref.ref_hyperadapt_gemm(x2, w, r, c).reshape(*lead, f)
    out = hyperadapt_gemm_pallas(x2, w, r, c, block_m=bm, block_f=bf,
                                 block_k=bk, interpret=_interpret(interpret))
    return out.reshape(*lead, f)


def hyperadapt_gemm_batched(x: jax.Array, w: jax.Array,
                            r_bank: jax.Array, c_bank: jax.Array,
                            ids: jax.Array, *,
                            interpret: bool | None = None) -> jax.Array:
    """Fused tenant-gather + row/col-scaled GEMM.  x: (B, S, d);
    r_bank: (A, d); c_bank: (A, f); ids: (B,)."""
    _, s, d = x.shape
    _, f = w.shape
    bs, bf, bk = gemm_tiles(s, d, f, 1)
    if not (bs and bf and bk):
        return ref.ref_hyperadapt_gemm_batched(x, w, r_bank, c_bank, ids)
    return hyperadapt_gemm_batched_pallas(x, w, r_bank, c_bank, ids,
                                          block_s=bs, block_f=bf,
                                          block_k=bk, interpret=interpret)


def hyperadapt_merge(w: jax.Array, r: jax.Array, c: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """HyperAdapt absorption W' = diag(r) w diag(c).  w: (d, f)."""
    f = w.shape[-1]
    if not lane_ok(f):
        return ref.ref_hyperadapt_merge(w, r, c)
    return hyperadapt_merge_pallas(w, r, c, interpret=_interpret(interpret))


# ---------------------------------------------------------------------------
# Hand-derived backwards (*_bwd ops).  Same contract as the forwards:
# tileable shapes hit the Pallas kernels, anything else falls back to
# the ref-AD oracles in ref.py.  Cotangent tuples are ordered like the
# forward op's primals; int operands (tenant ids) get float0 zeros.
# ---------------------------------------------------------------------------

def _float0_like(a):
    import numpy as np
    from jax.dtypes import float0
    return np.zeros(a.shape, float0)


def _bank_grad(bank: jax.Array, ids: jax.Array, ghat_seq: jax.Array):
    """Finish a bank cotangent from per-sequence dL/dû partials:
    scatter-add over tenant ids, then the ε-normalization chain rule per
    bank row (linear in dL/dû, so add-then-chain ≡ chain-then-add)."""
    gsum = jnp.zeros(bank.shape, jnp.float32).at[ids].add(ghat_seq)
    return norm_chain(bank.astype(jnp.float32), gsum).astype(bank.dtype)


def ether_reflect_bwd(x: jax.Array, u: jax.Array, g: jax.Array, *,
                      block_t: int = 256, interpret: bool | None = None):
    """(dx, du) for ether_reflect.  x/g: (..., d); u: (n, db)."""
    import math
    d = x.shape[-1]
    t = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    from repro.core import execute
    x2, g2 = x.reshape(t, d), g.reshape(t, d)
    if not execute.supports("ether_reflect", x, u):
        dx, du = ref.ref_ether_reflect_bwd(x2, u, g2)
        return dx.reshape(x.shape), du
    dx, du = ether_reflect_bwd_pallas(x2, u, g2,
                                      block_t=min(block_t, t),
                                      interpret=interpret)
    return dx.reshape(x.shape), du


def householder_gemm_bwd(x: jax.Array, w: jax.Array, u: jax.Array,
                         g: jax.Array, *, interpret: bool | None = None):
    """(dx, dw, du) for householder_gemm.  x: (..., d); w: (d, f);
    g: (..., f)."""
    import math
    d, f = w.shape
    lead = x.shape[:-1]
    t = math.prod(lead) if lead else 1
    from repro.core import execute
    x2, g2 = x.reshape(t, d), g.reshape(t, f)
    n, db = u.shape
    if not execute.supports("householder_gemm", x, w, u):
        dx, dw, du = ref.ref_householder_gemm_bwd(x2, w, u, g2)
        return dx.reshape(x.shape), dw, du
    bm, bf, bk = gemm_tiles(t, d, f, db)
    dx, du = reflect_gemm_dx_pallas(x2, w, u, g2, block_m=bm, block_d=bk,
                                    block_f=bf, interpret=interpret)
    dw = reflect_gemm_dw_pallas(x2, u, g2, block_m=bm, block_d=bk,
                                block_f=bf, w_dtype=w.dtype,
                                interpret=interpret)
    return dx.reshape(x.shape), dw, du


def etherplus_gemm_bwd(x: jax.Array, w: jax.Array, u1: jax.Array,
                       v1: jax.Array, u2: jax.Array | None,
                       v2: jax.Array | None, g: jax.Array, *,
                       interpret: bool | None = None):
    """(dx, dw, du1, dv1, du2, dv2) for the fused ETHER+ linear.

    Two-sided adapters recompute the pre-epilogue intermediate
    y0 = (H⁺x) @ W with the one-sided forward kernel (flash-attention
    style recompute — the forward never writes y0 to HBM)."""
    import math
    d, f = w.shape
    lead = x.shape[:-1]
    t = math.prod(lead) if lead else 1
    x2, g2 = x.reshape(t, d), g.reshape(t, f)
    from repro.core import execute
    n, db = u1.shape
    db_out = u2.shape[1] if u2 is not None else None
    bm, bf, bk = gemm_tiles(t, d, f, db, db_out)
    if not execute.supports("etherplus_gemm", x, w, u1, v1, u2, v2):
        out = ref.ref_etherplus_gemm_bwd(x2, w, u1, v1, u2, v2, g2)
        return (out[0].reshape(x.shape),) + tuple(out[1:])
    if u2 is None:
        dy0, du2, dv2 = g2, None, None
    else:
        y0 = etherplus_gemm_pallas(x2, w, u1, v1, block_m=bm, block_f=bf,
                                   block_k=bk, interpret=interpret)
        dy0, du2, dv2 = etherplus_reflect_bwd_pallas(y0, u2, v2, g2,
                                                     interpret=interpret)
    dx, du1, dv1 = reflect_gemm_dx_pallas(x2, w, u1, dy0, v1, block_m=bm,
                                          block_d=bk, block_f=bf,
                                          interpret=interpret)
    dw = reflect_gemm_dw_pallas(x2, u1, dy0, v1, block_m=bm, block_d=bk,
                                block_f=bf, w_dtype=w.dtype,
                                interpret=interpret)
    return dx.reshape(x.shape), dw, du1, dv1, du2, dv2


def ether_merge_bwd(w: jax.Array, u: jax.Array, g: jax.Array, *,
                    interpret: bool | None = None):
    """(dw, du) for ether_merge.  w/g: (d, f); u: (n, db)."""
    from repro.core import execute
    d, f = w.shape
    if not execute.supports("ether_merge", w, u):
        return ref.ref_ether_merge_bwd(w, u, g)
    return merge_left_bwd_pallas(w, u, g, block_f=_merge_block_f(f),
                                 interpret=interpret)


def etherplus_merge_bwd(w: jax.Array, u1: jax.Array, v1: jax.Array,
                        u2: jax.Array | None, v2: jax.Array | None,
                        g: jax.Array, *, interpret: bool | None = None):
    """(dw, du1, dv1, du2, dv2) for the ETHER+ absorption."""
    from repro.core import execute
    if not execute.supports("etherplus_merge", w, u1, v1, u2, v2):
        return ref.ref_etherplus_merge_bwd(w, u1, v1, u2, v2, g)
    if u2 is None:
        dw, du1, dv1 = merge_left_bwd_pallas(w, u1, g, v1,
                                             interpret=interpret)
        return dw, du1, dv1, None, None
    w1 = etherplus_merge_left_pallas(w, u1, v1,
                                     interpret=_interpret(interpret))
    dw1, du2, dv2 = merge_right_bwd_pallas(w1, u2, v2, g,
                                           interpret=interpret)
    dw, du1, dv1 = merge_left_bwd_pallas(w, u1, dw1, v1,
                                         interpret=interpret)
    return dw, du1, dv1, du2, dv2


def ether_reflect_batched_bwd(x: jax.Array, u_bank: jax.Array,
                              ids: jax.Array, g: jax.Array, *,
                              block_s: int = 128,
                              interpret: bool | None = None):
    """(dx, du_bank, dids) for the bank gather-and-reflect."""
    from repro.core import execute
    _, s, d = x.shape
    if not execute.supports("ether_reflect_batched", x, u_bank, ids):
        return ref.ref_ether_reflect_batched_bwd(x, u_bank, ids, g)
    dx, ghat = ether_reflect_batched_bwd_pallas(x, u_bank, ids, g,
                                                block_s=min(block_s, s),
                                                interpret=interpret)
    return dx, _bank_grad(u_bank, ids, ghat), _float0_like(ids)


def householder_gemm_batched_bwd(x: jax.Array, w: jax.Array,
                                 u_bank: jax.Array, ids: jax.Array,
                                 g: jax.Array, *,
                                 interpret: bool | None = None):
    """(dx, dw, du_bank, dids) for the fused bank GEMM."""
    from repro.core import execute
    _, s, d = x.shape
    _, f = w.shape
    _, n, db = u_bank.shape
    bs, bf, bk = gemm_tiles(s, d, f, db)
    if not execute.supports("householder_gemm_batched", x, w, u_bank, ids):
        return ref.ref_householder_gemm_batched_bwd(x, w, u_bank, ids, g)
    dx, ghat = householder_gemm_batched_bwd_pallas(
        x, w, u_bank, ids, g, block_s=bs, block_d=bk, block_f=bf,
        interpret=interpret)
    dw = householder_gemm_batched_dw_pallas(
        x, u_bank, ids, g, block_s=bs, block_d=bk, block_f=bf,
        w_dtype=w.dtype, interpret=interpret)
    return dx, dw, _bank_grad(u_bank, ids, ghat), _float0_like(ids)


def etherplus_reflect_batched_bwd(x: jax.Array, u_bank: jax.Array,
                                  v_bank: jax.Array, ids: jax.Array,
                                  g: jax.Array, *, block_s: int = 128,
                                  interpret: bool | None = None):
    """(dx, du_bank, dv_bank, dids) for the bank rank-2 reflect."""
    from repro.core import execute
    _, s, d = x.shape
    if not execute.supports("etherplus_reflect_batched", x, u_bank,
                            v_bank, ids):
        return ref.ref_etherplus_reflect_batched_bwd(x, u_bank, v_bank,
                                                     ids, g)
    dx, gu, gv = etherplus_reflect_batched_bwd_pallas(
        x, u_bank, v_bank, ids, g, block_s=min(block_s, s),
        interpret=interpret)
    return (dx, _bank_grad(u_bank, ids, gu), _bank_grad(v_bank, ids, gv),
            _float0_like(ids))


def _zero_u(d: int, dtype) -> jax.Array:
    """An all-zero hyperplane bank for dimension d: û(0) = 0, so the
    reflection GEMM kernels degenerate to plain matmuls.  Lets the new
    methods' backwards reuse the tiled dx/dw machinery for their
    unreflected cotangent GEMMs without new kernels."""
    bk0 = largest_divisor(d, 512)
    db0 = largest_divisor(bk0, 128)
    return jnp.zeros((d // db0, db0), dtype), bk0


def _plain_gemm(x2: jax.Array, w2: jax.Array, *,
                interpret: bool | None = None) -> jax.Array:
    """x2 @ w2 through householder_gemm with a zero hyperplane."""
    t, d = x2.shape
    f = w2.shape[1]
    u0, bk0 = _zero_u(d, x2.dtype)
    return householder_gemm_pallas(
        x2, w2, u0, block_m=largest_divisor(t, 128),
        block_f=largest_divisor(f, 128), block_k=bk0,
        interpret=_interpret(interpret))


def _plain_dw(x2: jax.Array, g2: jax.Array, w_dtype, *,
              interpret: bool | None = None) -> jax.Array:
    """x2ᵀ @ g2 through the reflection dw kernel with a zero hyperplane."""
    d = x2.shape[1]
    u0, bk0 = _zero_u(d, x2.dtype)
    return reflect_gemm_dw_pallas(x2, u0, g2, block_d=bk0, w_dtype=w_dtype,
                                  interpret=interpret)


def delora_gemm_bwd(x: jax.Array, w: jax.Array, a: jax.Array,
                    b: jax.Array, s: jax.Array, g: jax.Array, *,
                    interpret: bool | None = None):
    """(dx, dw, da, db, ds) for the fused DeLoRA linear.

    dx reuses the forward kernel on transposed operands
    (g Wᵀ + ((g bᵀ)·s) aᵀ — same fused shape); dw is the zero-hyperplane
    dw kernel; the adapter cotangents are thin rank-r contractions over
    the recomputed h = x a and p = g bᵀ (r ≪ d, jnp glue like the bank
    norm-chain in _bank_grad)."""
    import math
    d, f = w.shape
    lead = x.shape[:-1]
    t = math.prod(lead) if lead else 1
    x2, g2 = x.reshape(t, d), g.reshape(t, f)
    from repro.core import execute
    if not execute.supports("delora_gemm_bwd", x, w, a, b, s, g):
        out = ref.ref_delora_gemm_bwd(x2, w, a, b, s, g2)
        return (out[0].reshape(x.shape),) + tuple(out[1:])
    bm, bf, bk = gemm_tiles(t, f, d, 1)
    dx = delora_gemm_pallas(g2, w.T, b.T, a.T, s, block_m=bm, block_f=bf,
                            block_k=bk, interpret=_interpret(interpret))
    dw = _plain_dw(x2, g2, w.dtype, interpret=interpret)
    xf, gf = x2.astype(jnp.float32), g2.astype(jnp.float32)
    sf = s.astype(jnp.float32)
    h = xf @ a.astype(jnp.float32)                           # (t, r)
    p = gf @ b.astype(jnp.float32).T                         # (t, r)
    da = (xf.T @ (p * sf)).astype(a.dtype)
    db = ((h * sf).T @ gf).astype(b.dtype)
    ds = (h * p).sum(axis=0).astype(s.dtype)
    return dx.reshape(x.shape).astype(x.dtype), dw, da, db, ds


def delora_gemm_batched_bwd(x: jax.Array, w: jax.Array,
                            a_bank: jax.Array, b_bank: jax.Array,
                            s_bank: jax.Array, ids: jax.Array,
                            g: jax.Array, *,
                            interpret: bool | None = None):
    """(dx, dw, da_bank, db_bank, ds_bank, dids) for the bank DeLoRA
    GEMM.  dx reuses the batched forward kernel with per-tenant
    transposed adapters; adapter cotangents are per-sequence rank-r
    einsums scatter-added over tenant ids."""
    from repro.core import execute
    bsz, seq, d = x.shape
    _, f = w.shape
    if not execute.supports("delora_gemm_batched_bwd", x, w, a_bank,
                            b_bank, s_bank, ids, g):
        return ref.ref_delora_gemm_batched_bwd(x, w, a_bank, b_bank,
                                               s_bank, ids, g)
    bs, bf, bk = gemm_tiles(seq, f, d, 1)
    dx = delora_gemm_batched_pallas(
        g, w.T, jnp.swapaxes(b_bank, 1, 2), jnp.swapaxes(a_bank, 1, 2),
        s_bank, ids, block_s=bs, block_f=bf, block_k=bk,
        interpret=interpret).astype(x.dtype)
    dw = _plain_dw(x.reshape(bsz * seq, d), g.reshape(bsz * seq, f),
                   w.dtype, interpret=interpret)
    xf, gf = x.astype(jnp.float32), g.astype(jnp.float32)
    sf = s_bank[ids].astype(jnp.float32)                     # (B, r)
    h = jnp.einsum("bsd,bdr->bsr", xf, a_bank[ids].astype(jnp.float32))
    p = jnp.einsum("bsf,brf->bsr", gf, b_bank[ids].astype(jnp.float32))
    da_seq = jnp.einsum("bsd,bsr->bdr", xf, p * sf[:, None, :])
    db_seq = jnp.einsum("bsr,bsf->brf", h * sf[:, None, :], gf)
    ds_seq = (h * p).sum(axis=1)                             # (B, r)
    da = (jnp.zeros(a_bank.shape, jnp.float32).at[ids].add(da_seq)
          .astype(a_bank.dtype))
    db = (jnp.zeros(b_bank.shape, jnp.float32).at[ids].add(db_seq)
          .astype(b_bank.dtype))
    ds = (jnp.zeros(s_bank.shape, jnp.float32).at[ids].add(ds_seq)
          .astype(s_bank.dtype))
    return dx, dw, da, db, ds, _float0_like(ids)


def delora_merge_bwd(w: jax.Array, a: jax.Array, b: jax.Array,
                     s: jax.Array, g: jax.Array, *,
                     interpret: bool | None = None):
    """(dw, da, db, ds) for the DeLoRA absorption.  dw = g exactly (the
    op is w plus a w-independent term); everything else is a thin rank-r
    contraction, so this backend is pure jnp glue — no kernel to win."""
    gf = g.astype(jnp.float32)
    af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
    sf = s.astype(jnp.float32)
    gb = gf @ bf.T                                           # (d, r)
    da = (gb * sf).astype(a.dtype)
    db = ((af * sf).T @ gf).astype(b.dtype)
    ds = (af * gb).sum(axis=0).astype(s.dtype)
    return g.astype(w.dtype), da, db, ds


def hyperadapt_gemm_bwd(x: jax.Array, w: jax.Array, r: jax.Array,
                        c: jax.Array, g: jax.Array, *,
                        interpret: bool | None = None):
    """(dx, dw, dr, dc) for the fused HyperAdapt linear.

    z = (g·c) Wᵀ reuses the forward kernel transposed with the scales
    swapped (row scale c, unit column scale); the epilogue intermediate
    y0 = (x·r) W is recomputed the same way (never written in the
    forward); dw runs the zero-hyperplane dw kernel on pre-scaled
    operands."""
    import math
    d, f = w.shape
    lead = x.shape[:-1]
    t = math.prod(lead) if lead else 1
    x2, g2 = x.reshape(t, d), g.reshape(t, f)
    from repro.core import execute
    if not execute.supports("hyperadapt_gemm_bwd", x, w, r, c, g):
        out = ref.ref_hyperadapt_gemm_bwd(x2, w, r, c, g2)
        return (out[0].reshape(x.shape),) + tuple(out[1:])
    bm, bf, bk = gemm_tiles(t, f, d, 1)
    z = hyperadapt_gemm_pallas(g2, w.T, c, jnp.ones((d,), r.dtype),
                               block_m=bm, block_f=bf, block_k=bk,
                               interpret=_interpret(interpret))
    bm2, bf2, bk2 = gemm_tiles(t, d, f, 1)
    y0 = hyperadapt_gemm_pallas(x2, w, r, jnp.ones((f,), c.dtype),
                                block_m=bm2, block_f=bf2, block_k=bk2,
                                interpret=_interpret(interpret))
    xf, gf = x2.astype(jnp.float32), g2.astype(jnp.float32)
    zf = z.astype(jnp.float32)
    rf, cf = r.astype(jnp.float32), c.astype(jnp.float32)
    dx = (zf * rf[None, :]).astype(x.dtype)
    dr = (xf * zf).sum(axis=0).astype(r.dtype)
    dc = (y0.astype(jnp.float32) * gf).sum(axis=0).astype(c.dtype)
    dw = _plain_dw((xf * rf[None, :]).astype(x.dtype),
                   (gf * cf[None, :]).astype(g.dtype), w.dtype,
                   interpret=interpret)
    return dx.reshape(x.shape), dw, dr, dc


def hyperadapt_gemm_batched_bwd(x: jax.Array, w: jax.Array,
                                r_bank: jax.Array, c_bank: jax.Array,
                                ids: jax.Array, g: jax.Array, *,
                                interpret: bool | None = None):
    """(dx, dw, dr_bank, dc_bank, dids) for the bank HyperAdapt GEMM.
    Pre-scales per tenant in jnp (gathers are O(B·d)), then runs the
    zero-hyperplane GEMM/dw kernels on the flattened operands; scale
    cotangents are per-sequence reductions scatter-added over ids."""
    from repro.core import execute
    bsz, seq, d = x.shape
    _, f = w.shape
    if not execute.supports("hyperadapt_gemm_batched_bwd", x, w, r_bank,
                            c_bank, ids, g):
        return ref.ref_hyperadapt_gemm_batched_bwd(x, w, r_bank, c_bank,
                                                   ids, g)
    xf, gf = x.astype(jnp.float32), g.astype(jnp.float32)
    rs = r_bank[ids].astype(jnp.float32)                     # (B, d)
    cs = c_bank[ids].astype(jnp.float32)                     # (B, f)
    xr = (xf * rs[:, None, :]).astype(x.dtype).reshape(bsz * seq, d)
    gc = (gf * cs[:, None, :]).astype(g.dtype).reshape(bsz * seq, f)
    z = _plain_gemm(gc, w.T, interpret=interpret).reshape(bsz, seq, d)
    y0 = _plain_gemm(xr, w, interpret=interpret).reshape(bsz, seq, f)
    zf = z.astype(jnp.float32)
    dx = (zf * rs[:, None, :]).astype(x.dtype)
    dw = _plain_dw(xr, gc, w.dtype, interpret=interpret)
    dr_seq = (xf * zf).sum(axis=1)                           # (B, d)
    dc_seq = (y0.astype(jnp.float32) * gf).sum(axis=1)       # (B, f)
    dr = (jnp.zeros(r_bank.shape, jnp.float32).at[ids].add(dr_seq)
          .astype(r_bank.dtype))
    dc = (jnp.zeros(c_bank.shape, jnp.float32).at[ids].add(dc_seq)
          .astype(c_bank.dtype))
    return dx, dw, dr, dc, _float0_like(ids)


def hyperadapt_merge_bwd(w: jax.Array, r: jax.Array, c: jax.Array,
                         g: jax.Array, *,
                         interpret: bool | None = None):
    """(dw, dr, dc) for the HyperAdapt absorption.  The op is linear in
    w with the same row/col scaling, so dw reuses the forward merge
    kernel on g; dr/dc are single reductions of w⊙g."""
    from repro.core import execute
    if not execute.supports("hyperadapt_merge", w, r, c):
        return ref.ref_hyperadapt_merge_bwd(w, r, c, g)
    dw = hyperadapt_merge_pallas(g, r, c,
                                 interpret=_interpret(interpret))
    wg = w.astype(jnp.float32) * g.astype(jnp.float32)
    dr = (wg @ c.astype(jnp.float32)).astype(r.dtype)
    dc = (wg.T @ r.astype(jnp.float32)).astype(c.dtype)
    return dw.astype(w.dtype), dr, dc


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    q_offset: int = 0, interpret: bool | None = None
                    ) -> jax.Array:
    """Flash attention; falls back to exact ref for non-128-tileable S/T."""
    s, t = q.shape[2], k.shape[2]
    bq = 128 if s % 128 == 0 else (s if s <= 128 else 0)
    bk = 128 if t % 128 == 0 else (t if t <= 128 else 0)
    if not bq or not bk:
        return ref.ref_flash_attention(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, block_q=bq, block_k=bk,
                                  interpret=_interpret(interpret))


def ssd_chunked_pallas(xv, a, b, c, *, chunk: int = 128,
                       interpret: bool | None = None):
    """Full SSD via the Pallas intra-chunk kernel + XLA inter-chunk scan.

    xv: (B,S,H,P); a: (B,S,H); b/c: (B,S,G,N). Mirrors
    models.ssm.ssd_chunked (zero initial state); returns (y, final_state).
    """
    import jax
    B, S, H, P = xv.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    if S % chunk:
        return None  # caller falls back to the jnp path
    from repro.kernels.ssd_scan import ssd_chunk_pallas
    f32 = jnp.float32
    bh = jnp.repeat(b, rep, axis=2)
    ch = jnp.repeat(c, rep, axis=2)
    fold = lambda t: t.transpose(0, 2, 1, *range(3, t.ndim)).reshape(
        B * H, S, *t.shape[3:])
    xv2 = fold(xv.astype(f32))
    a2 = a.astype(f32).transpose(0, 2, 1).reshape(B * H, S)
    b2 = fold(bh.astype(f32))
    c2 = fold(ch.astype(f32))
    y_intra, states, decays = ssd_chunk_pallas(
        xv2, a2, b2, c2, chunk=chunk, interpret=_interpret(interpret))

    # inter-chunk recurrence (cheap, O(nc))
    def step(carry, inp):
        s_c, dec = inp
        new = dec[:, None, None] * carry + s_c
        return new, carry
    init = jnp.zeros((B * H, N, P), f32)
    final, prev = jax.lax.scan(
        step, init, (states.transpose(1, 0, 2, 3),
                     decays.transpose(1, 0)))
    prev = prev.transpose(1, 0, 2, 3)               # (BH, nc, N, P)
    # y_inter[t] = exp(cum_t) · C_t · prev_state(chunk of t)
    nc = S // chunk
    a4 = a2.reshape(B * H, nc, chunk)
    cum = jnp.cumsum(a4, axis=-1)
    c4 = c2.reshape(B * H, nc, chunk, N)
    y_inter = jnp.einsum("kcln,kcnp,kcl->kclp", c4, prev, jnp.exp(cum))
    y = y_intra.reshape(B * H, nc, chunk, P) + y_inter
    y = y.reshape(B * H, S, P).reshape(B, H, S, P).transpose(0, 2, 1, 3)
    return y.astype(xv.dtype), final.reshape(B, H, N, P)
