"""Shared neural building blocks (functional, pytree-params).

Every linear goes through :func:`dense`, which is where PEFT adapters
(ETHER et al.) attach — one integration point for the whole model zoo.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.transforms import PEFTConfig, adapted_dense

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def he_normal(rng, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[-2] if len(shape) >= 2 else shape[0]
    return jax.random.normal(rng, shape, dtype) * float(np.sqrt(2.0 / fan_in))


def lecun_normal(rng, shape, dtype, fan_in=None):
    fan_in = fan_in or (shape[-2] if len(shape) >= 2 else shape[0])
    return jax.random.normal(rng, shape, dtype) * float(np.sqrt(1.0 / fan_in))


def init_dense(rng, d_in: int, d_out: int, dtype, *, bias: bool = False,
               stack: tuple[int, ...] = ()) -> Params:
    """Kernel (…stack, d_in, d_out) + optional bias."""
    k = lecun_normal(rng, (*stack, d_in, d_out), dtype, fan_in=d_in)
    p: Params = {"kernel": k}
    if bias:
        p["bias"] = jnp.zeros((*stack, d_out), dtype)
    return p


def dense(p: Params, x: jax.Array, *, adapter: Optional[Params] = None,
          peft: Optional[PEFTConfig] = None) -> jax.Array:
    """y = adapted(W)ᵀx + b — the single PEFT attach point.

    ``peft.backend`` selects the execution backend (jnp / pallas / auto)
    for the ETHER hot ops; dispatch happens inside ``adapted_dense`` via
    ``core.execute``, so every model in the zoo inherits the kernel path
    without signature changes here."""
    return adapted_dense(x, p["kernel"], p.get("bias"), adapter, peft)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings / positions
# ---------------------------------------------------------------------------

def init_embedding(rng, vocab: int, d: int, dtype) -> Params:
    return {"table": jax.random.normal(rng, (vocab, d), dtype) * 0.02}


def embed(p: Params, tokens: jax.Array, compute_dtype) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0).astype(compute_dtype)


def logits_out(p: Params, x: jax.Array) -> jax.Array:
    """Tied or untied output head: x @ tableᵀ, f32 logits."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      p["table"].astype(jnp.float32))


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0):
    """Rotary embedding. x: (..., S, H, D) or (..., S, D); positions (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq        # (..., S, half)
    if x.ndim == ang.ndim + 1:                                    # heads axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d)
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), jnp.float32)


# ---------------------------------------------------------------------------
# Activations / MLPs
# ---------------------------------------------------------------------------

ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}


def init_glu_mlp(rng, d: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "gate_proj": init_dense(k1, d, d_ff, dtype),
        "up_proj": init_dense(k2, d, d_ff, dtype),
        "down_proj": init_dense(k3, d_ff, d, dtype),
    }


def glu_mlp(p: Params, x: jax.Array, act: str = "silu", *,
            adapters=None, peft=None) -> jax.Array:
    from repro.core.peft import get_adapter
    g = dense(p["gate_proj"], x, adapter=get_adapter(adapters, "gate_proj"),
              peft=peft)
    u = dense(p["up_proj"], x, adapter=get_adapter(adapters, "up_proj"),
              peft=peft)
    h = ACTS[act](g) * u
    return dense(p["down_proj"], h, adapter=get_adapter(adapters, "down_proj"),
                 peft=peft)


def init_mlp(rng, d: int, d_ff: int, dtype, *, bias: bool = False) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "up_proj": init_dense(k1, d, d_ff, dtype, bias=bias),
        "down_proj": init_dense(k2, d_ff, d, dtype, bias=bias),
    }


def mlp(p: Params, x: jax.Array, act: str = "gelu", *,
        adapters=None, peft=None) -> jax.Array:
    from repro.core.peft import get_adapter
    h = ACTS[act](dense(p["up_proj"], x,
                        adapter=get_adapter(adapters, "up_proj"), peft=peft))
    return dense(p["down_proj"], h,
                 adapter=get_adapter(adapters, "down_proj"), peft=peft)
