"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

Real-Gated Linear Recurrent Unit:
    r_t = σ(gate_a(u_t)),  i_t = σ(gate_x(u_t))          (per-head dense)
    log a_t = −c · softplus(Λ) ⊙ r_t
    h_t = a_t ⊙ h_{t−1} + sqrt(1 − a_t²) ⊙ (i_t ⊙ u_t)

TPU adaptation: prefill uses ``jax.lax.associative_scan`` over time (the
recurrence is linear given the gates — parallel depth log S), decode is a
single fused step. The surrounding block is Griffin's: dual-branch
(GeLU gate × conv→RG-LRU) with linear in/out projections, to which ETHER
attaches.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense, init_dense
from repro.models.ssm import _causal_conv

Params = dict[str, Any]

_C = 8.0  # Griffin's fixed gate sharpness


def init_rglru_block(rng, d_model: int, d_rnn: int, n_heads: int, dtype,
                     *, conv_width: int = 4) -> Params:
    hd = d_rnn // n_heads
    ks = jax.random.split(rng, 6)
    return {
        "in_x": init_dense(ks[0], d_model, d_rnn, dtype),
        "in_y": init_dense(ks[1], d_model, d_rnn, dtype),
        "conv": {"kernel": jax.random.normal(ks[2], (conv_width, d_rnn),
                                             dtype) * 0.1,
                 "bias": jnp.zeros((d_rnn,), dtype)},
        # per-head block-diagonal gates (Griffin §2.4)
        "gate_a": {"kernel": jax.random.normal(ks[3], (n_heads, hd, hd),
                                               dtype) / jnp.sqrt(hd)},
        "gate_x": {"kernel": jax.random.normal(ks[4], (n_heads, hd, hd),
                                               dtype) / jnp.sqrt(hd)},
        # Λ init so that a = exp(−c·softplus(Λ)) spans 0.9..0.999 at r=1
        # (Griffin init): softplus(Λ) = −log(a)/c ⇒ Λ = log(expm1(·)).
        "lam": jnp.log(jnp.expm1(
            -jnp.log(jnp.linspace(0.9, 0.999, d_rnn)) / _C) + 1e-12
        ).astype(jnp.float32),
        "out_proj": init_dense(ks[5], d_rnn, d_model, dtype),
    }


def _headwise(p_kernel: jax.Array, x: jax.Array, n_heads: int) -> jax.Array:
    """(B,S,d_rnn) → per-head dense → (B,S,d_rnn)."""
    b, s, d = x.shape
    hd = d // n_heads
    xh = x.reshape(b, s, n_heads, hd)
    yh = jnp.einsum("bshi,hij->bshj", xh, p_kernel.astype(x.dtype))
    return yh.reshape(b, s, d)


def rglru_scan(u: jax.Array, a_log: jax.Array,
               h0: Optional[jax.Array] = None):
    """Linear recurrence h_t = a_t h_{t−1} + b_t via associative scan.

    u: gated input b_t (B,S,D) f32; a_log: (B,S,D) f32 (log decay).
    Returns (h (B,S,D), final_state (B,D)).
    """
    a = jnp.exp(a_log)
    b = u
    if h0 is not None:
        # fold initial state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    av, bv = jax.lax.associative_scan(combine, (a, b), axis=1)
    return bv, bv[:, -1]


def rglru_block(p: Params, x: jax.Array, *, d_rnn: int, n_heads: int,
                cache: Optional[Params] = None, adapters=None, peft=None,
                true_lens: Optional[jax.Array] = None):
    """Griffin recurrent block. Returns (out, new_cache).

    cache (decode): {"conv": (B, W-1, d_rnn), "h": (B, d_rnn)}.

    ``true_lens`` (B,) makes right-padded prefill pad-invariant
    (DESIGN.md §10): pad positions become identity state updates
    (``a_t → 1`` i.e. ``log a_t → 0``, gated input ``→ 0``) and the
    conv tail streams the last *real* inputs.  The returned state is
    gathered at position ``true_lens - 1`` rather than read off the
    scan's last (padded) position: identity pad steps preserve the
    state *mathematically*, but ``associative_scan``'s combine tree
    regroups under a longer sequence, so the propagated value can
    differ from the unpadded oracle in the last ulp — the gather keeps
    it bitwise-equal (f32).
    """
    y_branch = jax.nn.gelu(dense(p, "in_y", x, adapters=adapters, peft=peft))
    u = dense(p, "in_x", x, adapters=adapters, peft=peft)
    conv_state = cache["conv"] if cache is not None else None
    u, new_conv = _causal_conv(u, p["conv"]["kernel"], p["conv"]["bias"],
                               conv_state, true_lens=true_lens)

    r = jax.nn.sigmoid(_headwise(p["gate_a"]["kernel"], u, n_heads)
                       .astype(jnp.float32))
    i = jax.nn.sigmoid(_headwise(p["gate_x"]["kernel"], u, n_heads)
                       .astype(jnp.float32))
    a_log = -_C * jax.nn.softplus(p["lam"])[None, None] * r     # ≤ 0
    gated = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * a_log), 1e-12, 1.0))
    b_t = gated * (i * u.astype(jnp.float32))
    if true_lens is not None:
        tl = jnp.asarray(true_lens, jnp.int32)
        valid = (jnp.arange(x.shape[1])[None] < tl[:, None])    # (B,S)
        a_log = jnp.where(valid[..., None], a_log, 0.0)          # a_t = 1
        b_t = jnp.where(valid[..., None], b_t, 0.0)              # no input

    if cache is not None and x.shape[1] == 1:
        h_prev = cache["h"].astype(jnp.float32)
        h = jnp.exp(a_log[:, 0]) * h_prev + b_t[:, 0]
        hs = h[:, None]
        final = h
    else:
        h0 = cache["h"] if cache is not None else None
        hs, final = rglru_scan(b_t, a_log, h0)
        if true_lens is not None:
            final = jnp.take_along_axis(
                hs, jnp.broadcast_to((tl - 1)[:, None, None],
                                     (hs.shape[0], 1, hs.shape[2])),
                axis=1)[:, 0]

    out = hs.astype(x.dtype) * y_branch
    out = dense(p, "out_proj", out, adapters=adapters, peft=peft)
    return out, {"conv": new_conv.astype(x.dtype),
                 "h": final.astype(jnp.float32)}
