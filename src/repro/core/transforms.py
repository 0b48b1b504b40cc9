"""The ETHER transform family (ICML 2024) and its in-paper baselines.

Conventions
-----------
* Weights are stored as ``W: (d_in, f_out)`` and dense layers compute
  ``y = x @ W + b`` (row-vector form of the paper's ``Wᵀx + b``).
* A multiplicative transform acts on the *input* dimension from the left,
  ``W' = T_B · W`` (block-diagonal ``T_B``), which in row form is
  ``y = (x @ T_B) @ W`` whenever ``T_B`` is symmetric (H and H⁺ both are).
* Block-diagonal structure: ``n`` blocks of size ``db = d/n``; arrays are
  kept *factored* — we never materialize the (d × d) transform outside of
  tests/metrics and the paper-literal FLOPs benchmark.

Three execution modes (see DESIGN.md §3 — hardware adaptation):

``activation``  (beyond-paper, TPU-native)
    Reflect the activations: ``Hx = x − 2û(ûᵀx)`` costs O(tokens·d); the
    GEMM runs on the *frozen* weight so no transformed weight ever exists.
    Exact — H is symmetric, so (H_B W)ᵀ x = Wᵀ (H_B x).

``weight``  (paper-faithful, factored)
    Rank-1 blockwise update ``W_i − 2 û_i (û_iᵀ W_i)``: O(d·f) regardless
    of n.  Used for the reproduction baseline and for merging.

``blockgemm``  (paper-literal §3.4)
    Materializes the n (db × db) Householder blocks and performs n block
    GEMMs — O(d²f/n) FLOPs, exactly the accounting in paper Table 1.
    Exists so benchmarks/table1_flops.py can reproduce the table.

Orthogonally to the *mode*, ``PEFTConfig.backend`` selects the
*implementation* of the hot ops (jnp reference einsums vs the Pallas
TPU kernels vs per-shape auto-selection); ``adapted_dense`` and
``merge_weight`` dispatch through :mod:`repro.core.execute`.

Method dispatch itself lives in :mod:`repro.core.methods`: every PEFT
method is a :class:`repro.core.methods.PEFTMethod` in a registry, and
the public entry points here (``init_adapter`` / ``adapted_dense`` /
``merge_weight`` / ``adapter_param_count`` / ``materialize_transform``)
are stable delegating facades over it.  This module keeps ``PEFTConfig``
and the jnp math primitives the methods' "jnp" backends are built from.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import execute
from repro.core import methods as _methods

Params = dict[str, Any]

_EPS = 1e-8

# Registration-ordered method names (DESIGN.md §15); kept as a module
# constant for backward compatibility — the registry is the source.
METHODS = _methods.available()


@dataclasses.dataclass(frozen=True)
class PEFTConfig:
    """Configuration for one PEFT method application."""

    method: str = "ether"          # one of METHODS
    n_blocks: int = 32             # ETHER/ETHER+/OFT/Naive diagonal blocks
    rank: int = 8                  # LoRA / VeRA rank
    alpha: float = 8.0             # LoRA scaling numerator (alpha/rank)
    mode: str = "activation"       # activation | weight | blockgemm
    # '+'-separated regexes of param paths to adapt; models match their
    # linear names against this.
    targets: str = "q_proj+k_proj+v_proj+o_proj+gate_proj+up_proj+down_proj"
    adapter_dtype: str = "float32"
    # Double-sided application for ETHER+ (paper default; App. D.2 ablates).
    two_sided: bool = True
    # Execution backend for the ETHER hot paths (DESIGN.md §3):
    # "jnp" (reference einsums), "pallas" (TPU kernels), or "auto"
    # (pallas when shapes tile, jnp fallback). Dispatch happens in
    # core.execute; serving configs opt into "auto".
    backend: str = "jnp"

    def __post_init__(self):
        _methods.get(self.method)   # raises UnknownMethodError (ValueError)
        if self.mode not in ("activation", "weight", "blockgemm"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.backend not in execute.BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")


def resolve_blocks(n: int, dim: int) -> int:
    """Largest divisor of ``dim`` that is <= n (paper requires n | d)."""
    n = max(1, min(n, dim))
    while dim % n:
        n -= 1
    return n


def _unit(u: jax.Array) -> jax.Array:
    """Normalize the last axis to unit length (paper: û = u/|u|)."""
    return u / (jnp.linalg.norm(u, axis=-1, keepdims=True) + _EPS)


def _blockify(x: jax.Array, n: int) -> jax.Array:
    """(..., d) -> (..., n, d/n)."""
    return x.reshape(*x.shape[:-1], n, x.shape[-1] // n)


def _deblockify(x: jax.Array) -> jax.Array:
    """(..., n, db) -> (..., n*db)."""
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


# ---------------------------------------------------------------------------
# Blockwise rank-1 primitives (shared by ETHER / ETHER+)
# ---------------------------------------------------------------------------

def reflect_activation(x: jax.Array, u: jax.Array, *, coeff: float = 2.0,
                       sign: float = -1.0) -> jax.Array:
    """Blockwise ``x + sign*coeff*û(ûᵀx)`` on the last dim of x.

    u: (n, db) raw (unnormalized) hyperplane vectors. coeff=2,sign=-1 gives
    the Householder reflection H_B x.
    """
    n, db = u.shape
    uh = _unit(u).astype(x.dtype)
    xb = _blockify(x, n)                              # (..., n, db)
    proj = jnp.einsum("...nb,nb->...n", xb, uh)       # (..., n)
    xb = xb + (sign * coeff) * proj[..., None] * uh
    return _deblockify(xb)


def reflect_activation_batched(x: jax.Array, u_bank: jax.Array,
                               ids: jax.Array, *, coeff: float = 2.0,
                               sign: float = -1.0) -> jax.Array:
    """Multi-tenant serving: per-sequence adapters from a bank.

    x: (B, S, d); u_bank: (num_adapters, n, db); ids: (B,) int32.
    Gathers each sequence's hyperplane vectors and reflects — the batched
    analogue of :func:`reflect_activation`. ETHER's tiny adapter size makes
    thousands-of-tenants banks a few MB of HBM (DESIGN.md §2).
    """
    _, n, db = u_bank.shape
    # Gather each request's vectors FIRST, then normalize: O(B·d) per
    # call instead of normalizing the whole O(num_adapters·d) bank.
    u = _unit(u_bank[ids]).astype(x.dtype)            # (B, n, db)
    xb = _blockify(x, n)                              # (B, S, n, db)
    proj = jnp.einsum("bsnd,bnd->bsn", xb, u)
    xb = xb + (sign * coeff) * proj[..., None] * u[:, None]
    return _deblockify(xb)


def etherplus_activation(x: jax.Array, u: jax.Array,
                         v: jax.Array) -> jax.Array:
    """Blockwise ``H⁺x = x − û(ûᵀx) + v̂(v̂ᵀx)`` — a true rank-2 update.

    NOT two sequential reflections: (I+vvᵀ)(I−uuᵀ) has a −vvᵀuuᵀ cross
    term the paper's H⁺ does not; both projections read the original x.
    """
    n, db = u.shape
    uh = _unit(u).astype(x.dtype)
    vh = _unit(v).astype(x.dtype)
    xb = _blockify(x, n)
    pu = jnp.einsum("...nb,nb->...n", xb, uh)
    pv = jnp.einsum("...nb,nb->...n", xb, vh)
    xb = xb - pu[..., None] * uh + pv[..., None] * vh
    return _deblockify(xb)


def etherplus_activation_batched(x: jax.Array, u_bank: jax.Array,
                                 v_bank: jax.Array,
                                 ids: jax.Array) -> jax.Array:
    """Multi-tenant ETHER+ serving: per-sequence rank-2 updates from a
    bank pair.

    x: (B, S, d); u_bank/v_bank: (num_adapters, n, db); ids: (B,) int32.
    The batched analogue of :func:`etherplus_activation` — both
    projections read the original x.  Gathers each request's vectors
    FIRST, then normalizes: O(B·d) per call, not O(num_adapters·d).
    """
    _, n, db = u_bank.shape
    u = _unit(u_bank[ids]).astype(x.dtype)            # (B, n, db)
    v = _unit(v_bank[ids]).astype(x.dtype)
    xb = _blockify(x, n)                              # (B, S, n, db)
    pu = jnp.einsum("bsnd,bnd->bsn", xb, u)
    pv = jnp.einsum("bsnd,bnd->bsn", xb, v)
    xb = xb - pu[..., None] * u[:, None] + pv[..., None] * v[:, None]
    return _deblockify(xb)


def etherplus_weight(W: jax.Array, u: jax.Array, v: jax.Array,
                     side: str = "left") -> jax.Array:
    """Blockwise ``H⁺W`` (side='left') or ``W H̃⁺`` (side='right') as a
    single rank-2 update from the original W (see etherplus_activation)."""
    n, db = u.shape
    uh = _unit(u).astype(W.dtype)
    vh = _unit(v).astype(W.dtype)
    d, f = W.shape
    if side == "left":
        Wb = W.reshape(n, db, f)
        pu = jnp.einsum("nb,nbf->nf", uh, Wb)
        pv = jnp.einsum("nb,nbf->nf", vh, Wb)
        Wb = Wb - uh[:, :, None] * pu[:, None, :] \
            + vh[:, :, None] * pv[:, None, :]
        return Wb.reshape(d, f)
    Wb = W.reshape(d, n, db)
    # multiply+reduce, NOT einsum("dnb,nb->dn"): the d-major batched
    # einsum lowers to a per-(d,n) matvec loop on CPU (~3× slower than
    # the fused elementwise reduction at d=4096 — the BENCH_kernels.json
    # merge cliff); both projections fuse into one read of W this way.
    pu = (Wb * uh[None]).sum(-1)
    pv = (Wb * vh[None]).sum(-1)
    Wb = Wb - pu[..., None] * uh[None] + pv[..., None] * vh[None]
    return Wb.reshape(d, f)


def reflect_weight(W: jax.Array, u: jax.Array, *, coeff: float = 2.0,
                   sign: float = -1.0, side: str = "left") -> jax.Array:
    """Factored blockwise rank-1 transform of a weight matrix.

    side='left':  W' = T_B W   (T on the d_in dimension, W: (d, f))
    side='right': W' = W T_B   (T on the f_out dimension)
    """
    n, db = u.shape
    uh = _unit(u).astype(W.dtype)
    if side == "left":
        d, f = W.shape
        Wb = W.reshape(n, db, f)
        proj = jnp.einsum("nb,nbf->nf", uh, Wb)       # ûᵀ W_i
        Wb = Wb + (sign * coeff) * uh[:, :, None] * proj[:, None, :]
        return Wb.reshape(d, f)
    else:
        d, f = W.shape
        Wb = W.reshape(d, n, db)
        # W_j u_j as multiply+reduce — see etherplus_weight for why the
        # d-major einsum form is a CPU cliff.
        proj = (Wb * uh[None]).sum(-1)
        Wb = Wb + (sign * coeff) * proj[..., None] * uh[None]
        return Wb.reshape(d, f)


def householder_blocks(u: jax.Array, *, coeff: float = 2.0,
                       sign: float = -1.0) -> jax.Array:
    """Materialize the n (db × db) Householder blocks (paper-literal)."""
    n, db = u.shape
    uh = _unit(u)
    eye = jnp.eye(db, dtype=uh.dtype)
    return eye[None] + (sign * coeff) * jnp.einsum("ni,nj->nij", uh, uh)


def block_diag_matmul(blocks: jax.Array, W: jax.Array,
                      side: str = "left") -> jax.Array:
    """n explicit block GEMMs: diag(blocks) @ W — the paper's §3.4 scheme."""
    n, db, _ = blocks.shape
    if side == "left":
        d, f = W.shape
        Wb = W.reshape(n, db, f)
        out = jnp.einsum("nij,njf->nif", blocks.astype(W.dtype), Wb)
        return out.reshape(d, f)
    else:
        d, f = W.shape
        Wb = W.reshape(d, n, db)
        out = jnp.einsum("dni,nij->dnj", Wb, blocks.astype(W.dtype))
        return out.reshape(d, f)


def materialize_block_diag(blocks: jax.Array) -> jax.Array:
    """(n, db, db) -> dense (n*db, n*db) block-diagonal matrix (tests only)."""
    n, db, _ = blocks.shape
    out = jnp.zeros((n * db, n * db), blocks.dtype)
    for i in range(n):
        out = out.at[i * db:(i + 1) * db, i * db:(i + 1) * db].set(blocks[i])
    return out


# ---------------------------------------------------------------------------
# DeLoRA / HyperAdapt jnp primitives (the "jnp" backends of their ops)
# ---------------------------------------------------------------------------

def delora_gemm(x: jax.Array, W: jax.Array, a: jax.Array, b: jax.Array,
                s: jax.Array) -> jax.Array:
    """DeLoRA fused dense: ``y = xW + ((x a)·s) b``.

    The per-component normalized scale s_j = (λ/r)/(‖a_j‖‖b_j‖+ε) is a
    *primal* computed by the method layer (methods.DeLoRAMethod.scale),
    so this op — and its hand-derived backward — never re-derives the
    norm chain.  x: (..., d); W: (d, f); a: (d, r); b: (r, f); s: (r,).
    """
    y = x @ W.astype(x.dtype)
    h = (x @ a.astype(x.dtype)) * s.astype(x.dtype)
    return y + h @ b.astype(x.dtype)


def delora_gemm_batched(x: jax.Array, W: jax.Array, a_bank: jax.Array,
                        b_bank: jax.Array, s_bank: jax.Array,
                        ids: jax.Array) -> jax.Array:
    """Multi-tenant DeLoRA: per-sequence low-rank updates from a bank.

    x: (B, S, d); a_bank: (A, d, r); b_bank: (A, r, f); s_bank: (A, r);
    ids: (B,) int32.  Shared frozen GEMM + per-request gathered thin
    GEMMs — the batched analogue of :func:`delora_gemm`.
    """
    a = a_bank[ids].astype(x.dtype)                   # (B, d, r)
    bb = b_bank[ids].astype(x.dtype)                  # (B, r, f)
    s = s_bank[ids].astype(x.dtype)                   # (B, r)
    y = x @ W.astype(x.dtype)
    h = jnp.einsum("bsd,bdr->bsr", x, a) * s[:, None, :]
    return y + jnp.einsum("bsr,brf->bsf", h, bb)


def delora_merge(W: jax.Array, a: jax.Array, b: jax.Array,
                 s: jax.Array) -> jax.Array:
    """Absorb DeLoRA: ``W' = W + (a·s) b`` (s pre-normalized, see above)."""
    return W + (a.astype(W.dtype) * s.astype(W.dtype)) @ b.astype(W.dtype)


def hyperadapt_gemm(x: jax.Array, W: jax.Array, r: jax.Array,
                    c: jax.Array) -> jax.Array:
    """HyperAdapt dense: ``y = ((x·r) W)·c`` — diag(r) W diag(c) applied
    without materializing the scaled weight.  r: (d,); c: (f,)."""
    y = (x * r.astype(x.dtype)) @ W.astype(x.dtype)
    return y * c.astype(x.dtype)


def hyperadapt_gemm_batched(x: jax.Array, W: jax.Array, r_bank: jax.Array,
                            c_bank: jax.Array, ids: jax.Array) -> jax.Array:
    """Multi-tenant HyperAdapt: per-sequence row/col scales from a bank.

    x: (B, S, d); r_bank: (A, d); c_bank: (A, f); ids: (B,) int32.
    """
    r = r_bank[ids].astype(x.dtype)                   # (B, d)
    c = c_bank[ids].astype(x.dtype)                   # (B, f)
    y = (x * r[:, None, :]) @ W.astype(x.dtype)
    return y * c[:, None, :]


def hyperadapt_merge(W: jax.Array, r: jax.Array, c: jax.Array) -> jax.Array:
    """Absorb HyperAdapt: ``W' = diag(r) W diag(c)`` (elementwise)."""
    return W * r.astype(W.dtype)[:, None] * c.astype(W.dtype)[None, :]


# ---------------------------------------------------------------------------
# Per-method adapter init
# ---------------------------------------------------------------------------

def init_adapter(rng: jax.Array, method: str, d_in: int, d_out: int,
                 cfg: PEFTConfig) -> Params:
    """Create the trainable adapter parameters for one (d_in × d_out) linear."""
    return _methods.get(method).init(rng, d_in, d_out, cfg)


def _vera_frozen(seed: jax.Array, d_in: int, d_out: int, r: int, dtype):
    seed = jax.lax.stop_gradient(seed).astype(jnp.int32)
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    # Kaiming-uniform scaled by matrix dim (paper App. C.4).
    lim_a = float(np.sqrt(3.0 / d_in))
    lim_b = float(np.sqrt(3.0 / r))
    A = jax.random.uniform(k1, (d_in, r), dtype, -lim_a, lim_a)
    B = jax.random.uniform(k2, (r, d_out), dtype, -lim_b, lim_b)
    return A, B


# ---------------------------------------------------------------------------
# Adapted dense layer — the single entry point models call
# ---------------------------------------------------------------------------

def adapted_dense(x: jax.Array, W: jax.Array, b: Optional[jax.Array],
                  adapter: Optional[Params], cfg: Optional[PEFTConfig]) -> jax.Array:
    """Compute the adapted forward ``y = (T_L W T_R)ᵀx + ΔWᵀx + b``.

    With ``adapter=None`` (or empty) this is a plain dense layer.
    Dispatches on cfg.method and cfg.mode. x: (..., d_in); W: (d_in, d_out).
    Where an adapter applies, the work runs under the named scope
    ``adapted_dense`` (its backward too: the custom_vjp backwards are
    traced inside the forward's scope), so a profile finds the adapted
    projections whichever backend or kernel computes them.
    """
    if not adapter or cfg is None or cfg.method == "full":
        y = x @ W.astype(x.dtype)
        return y if b is None else y + b.astype(x.dtype)

    m = _methods.get(cfg.method)
    with jax.named_scope("adapted_dense"):
        if "ids" in adapter:
            # Multi-tenant bank (core.peft.AdapterBank): every adapter
            # leaf is the whole (num_adapters, ...) bank; each batch row
            # applies its own tenant's adapter (DESIGN.md §2).
            _check_bank_inputs(x, adapter, cfg)
            y = m.bank_dense(x, W, adapter, cfg)
        else:
            y = m.dense(x, W, adapter, cfg)
        return y if b is None else y + b.astype(x.dtype)


def _check_bank_inputs(x: jax.Array, adapter: Params,
                       cfg: PEFTConfig) -> None:
    """Shared AdapterBank trace-time validation (all bank methods)."""
    if cfg.mode != "activation":
        raise ValueError(
            "AdapterBank serving requires mode='activation' "
            f"(got {cfg.mode!r}); merge a single tenant via "
            "bank.select(i) + merge_params instead")
    if x.ndim != 3 or x.shape[0] != adapter["ids"].shape[0]:
        raise ValueError(
            f"bank adapters need per-request (B, S, d) inputs; "
            f"got x {x.shape} for ids {adapter['ids'].shape}")


# ---------------------------------------------------------------------------
# Merging (inference absorption) & materialization (metrics/tests)
# ---------------------------------------------------------------------------

def merge_weight(W: jax.Array, adapter: Optional[Params], cfg: PEFTConfig,
                 *, literal: bool = False) -> jax.Array:
    """Absorb the adapter into W — zero-latency inference (paper §3.1)."""
    if adapter is None or cfg.method == "full":
        return W
    return _methods.get(cfg.method).merge(W, adapter, cfg, literal=literal)


def _addmul(pair):
    """Combine (I−uuᵀ) and (+vvᵀ−I+I) factored blocks: H⁺ = B_u + B_v − I."""
    Hu, Hv = pair
    n, db, _ = Hu.shape
    eye = jnp.eye(db, dtype=Hu.dtype)[None]
    return Hu + Hv - eye


def materialize_transform(adapter: Params, cfg: PEFTConfig, d_in: int,
                          d_out: int):
    """Dense left/right transform matrices for metrics — small dims only.

    Returns (T_left (d_in,d_in) or None, T_right (d_out,d_out) or None).
    Additive methods (lora/vera) return (None, None).
    """
    return _methods.get(cfg.method).materialize(adapter, cfg, d_in, d_out)


# ---------------------------------------------------------------------------
# Parameter accounting (paper Tables 2–5 '#params' columns)
# ---------------------------------------------------------------------------

def adapter_param_count(method: str, d_in: int, d_out: int,
                        cfg: PEFTConfig) -> int:
    """Trainable parameter count for one adapted linear.

    Note (paper App. C): OFT's *reported* counts follow Qiu et al.'s
    convention of counting the skew-symmetric storage (half the raw R
    entries); we expose both via ``oft`` (reported) math here.
    """
    return _methods.get(method).param_count(d_in, d_out, cfg)
