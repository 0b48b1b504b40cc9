"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) block.

TPU adaptation: the SSD *chunked dual form* — intra-chunk attention-like
matmuls (MXU-friendly) + an inter-chunk linear state scan — instead of the
GPU kernel's warp-level scan. O(S·L) compute / O(S) memory with chunk
length L, which is what makes `long_500k` viable for this family.

ETHER attaches to ``in_proj`` / ``out_proj`` (the (d×f) linears); conv,
Δ, A, D have no d×f structure and stay frozen (DESIGN.md §5).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense, init_dense, init_rmsnorm, rmsnorm

Params = dict[str, Any]


def ssm_dims(d_model: int, *, expand: int = 2, headdim: int = 64,
             d_state: int = 128, n_groups: int = 1, conv_width: int = 4):
    d_inner = expand * d_model
    return dict(d_inner=d_inner, headdim=headdim,
                n_heads=d_inner // headdim, d_state=d_state,
                n_groups=n_groups, conv_width=conv_width)


def init_mamba2(rng, d_model: int, dtype, **kw) -> Params:
    dims = ssm_dims(d_model, **kw)
    di, h, g, n, w = (dims["d_inner"], dims["n_heads"], dims["n_groups"],
                      dims["d_state"], dims["conv_width"])
    k1, k2, k3 = jax.random.split(rng, 3)
    d_in_proj = 2 * di + 2 * g * n + h          # z, x, B, C, dt
    conv_ch = di + 2 * g * n
    return {
        "in_proj": init_dense(k1, d_model, d_in_proj, dtype),
        "conv": {"kernel": jax.random.normal(k2, (w, conv_ch), dtype) * 0.1,
                 "bias": jnp.zeros((conv_ch,), dtype)},
        "a_log": jnp.zeros((h,), jnp.float32),          # A = -exp(a_log) = -1
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": init_rmsnorm(di, dtype),
        "out_proj": init_dense(k3, di, d_model, dtype),
    }


def _causal_conv(x: jax.Array, kernel: jax.Array, bias: jax.Array,
                 state: Optional[jax.Array] = None,
                 true_lens: Optional[jax.Array] = None):
    """Depthwise causal conv1d. x: (B, S, C); kernel: (W, C).

    Returns (y, new_state) where state holds the last W-1 inputs for
    streaming decode.  With right-padded prompts, ``true_lens`` (B,)
    makes the streamed tail hold the last W-1 *real* inputs per row
    (DESIGN.md §10): ctx index ``true_lens[b]`` is the first of them,
    since ctx prepends W-1 state/zero entries before x.  Prompts
    shorter than W-1 naturally pick up the leading zero-state entries
    — exactly what an unpadded prompt of that length would stream.
    """
    w = kernel.shape[0]
    if state is None:
        ctx = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    else:
        ctx = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(ctx[:, i:i + x.shape[1]] * kernel[i][None, None]
            for i in range(w))
    if w <= 1:
        new_state = jnp.zeros((x.shape[0], 0, x.shape[2]), x.dtype)
    elif true_lens is None:
        new_state = ctx[:, -(w - 1):]
    else:
        new_state = jax.vmap(
            lambda c, t: jax.lax.dynamic_slice_in_dim(c, t, w - 1, axis=0)
        )(ctx, jnp.asarray(true_lens, jnp.int32))
    return jax.nn.silu(y + bias[None, None]), new_state


def ssd_chunked(xv, a, b, c, *, chunk: int = 256,
                initial_state: Optional[jax.Array] = None):
    """SSD chunked dual form.

    xv: (B,S,H,P) Δ-scaled inputs; a: (B,S,H) log-decay (≤0);
    b,c: (B,S,G,N). Returns (y (B,S,H,P), final_state (B,H,N,P)).
    """
    B, S, H, P = xv.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    L = min(chunk, S)
    S0 = S
    if S % L:
        # zero-pad to a chunk multiple: a=0 ⇒ decay exp(0)=1 and b·x=0,
        # so padded steps pass the state through exactly.
        pad = L - S % L
        z3 = ((0, 0), (0, pad), (0, 0))
        xv = jnp.pad(xv, z3 + ((0, 0),))
        a = jnp.pad(a, z3)
        b = jnp.pad(b, z3 + ((0, 0),))
        c = jnp.pad(c, z3 + ((0, 0),))
        S = S + pad
    nc = S // L

    f32 = jnp.float32
    xv_ = xv.astype(f32).reshape(B, nc, L, H, P)
    a_ = a.astype(f32).reshape(B, nc, L, H)
    bh = jnp.repeat(b.astype(f32), rep, axis=2).reshape(B, nc, L, H, N)
    ch = jnp.repeat(c.astype(f32), rep, axis=2).reshape(B, nc, L, H, N)

    cum = jnp.cumsum(a_, axis=2)                           # (B,nc,L,H)

    # --- intra-chunk (attention-like, MXU matmuls) ---
    cb = jnp.einsum("bclhn,bcshn->bchls", ch, bh)          # (B,nc,H,L,L)
    seg = cum[..., None, :, :].transpose(0, 1, 4, 2, 3)    # unused helper
    del seg
    decay = jnp.exp(cum.transpose(0, 1, 3, 2)[..., :, None]
                    - cum.transpose(0, 1, 3, 2)[..., None, :])  # (B,nc,H,L,L)
    causal = jnp.tril(jnp.ones((L, L), bool))
    scores = jnp.where(causal[None, None, None], cb * decay, 0.0)
    y_intra = jnp.einsum("bchls,bcshp->bclhp", scores, xv_)

    # --- chunk summary states ---
    w_in = jnp.exp(cum[:, :, -1:, :] - cum)                # (B,nc,L,H)
    states = jnp.einsum("bcshn,bcsh,bcshp->bchnp", bh, w_in, xv_)

    # --- inter-chunk scan ---
    chunk_decay = jnp.exp(cum[:, :, -1])                   # (B,nc,H)

    def step(carry, inp):
        s_c, dec = inp                                     # (B,H,N,P),(B,H)
        new = dec[..., None, None] * carry + s_c
        return new, carry                                  # emit *previous*

    init = (jnp.zeros((B, H, N, P), f32) if initial_state is None
            else initial_state.astype(f32))
    final, prev_states = jax.lax.scan(
        step, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)     # (B,nc,H,N,P)

    y_inter = jnp.einsum("bclhn,bchnp,bclh->bclhp", ch, prev_states,
                         jnp.exp(cum))
    y = (y_intra + y_inter).reshape(B, S, H, P)[:, :S0]
    return y.astype(xv.dtype), final


def mamba2_block(p: Params, x: jax.Array, *, d_model: int,
                 cache: Optional[Params] = None, chunk: int = 256,
                 adapters=None, peft=None,
                 true_lens: Optional[jax.Array] = None, **kw):
    """Full Mamba-2 mixer. x: (B, S, d_model).

    cache (decode): {"conv": (B, W-1, C), "ssm": (B, H, N, P)}.
    Returns (out, new_cache).

    ``true_lens`` (B,) makes right-padded prefill pad-invariant
    (DESIGN.md §10): pad positions become identity state updates —
    log-decay ``a → 0`` (decay exp(0)=1 passes the state through) and
    ``xv → 0`` (no injection), the exact mechanism ``ssd_chunked``
    already uses for its own chunk-multiple padding — and the streamed
    conv tail is gathered at the last *real* inputs.  The returned
    state is bitwise-equal (f32) to the unpadded prompt's state.
    """
    dims = ssm_dims(d_model, **kw)
    di, h, g, n, pd = (dims["d_inner"], dims["n_heads"], dims["n_groups"],
                       dims["d_state"], dims["headdim"])
    B, S, _ = x.shape

    zxbcdt = dense(p, "in_proj", x, adapters=adapters, peft=peft)
    z, xbc, dt_raw = jnp.split(zxbcdt, [di, di + di + 2 * g * n], axis=-1)

    conv_state = cache["conv"] if cache is not None else None
    xbc, new_conv = _causal_conv(xbc, p["conv"]["kernel"], p["conv"]["bias"],
                                 conv_state, true_lens=true_lens)
    xs, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
    b = b.reshape(B, S, g, n)
    c = c.reshape(B, S, g, n)
    xh = xs.reshape(B, S, h, pd)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None])        # (B,S,H)
    a = -jnp.exp(p["a_log"])[None, None] * dt                # log-decay ≤ 0
    xv = xh.astype(jnp.float32) * dt[..., None]
    if true_lens is not None:
        valid = (jnp.arange(S)[None] <
                 jnp.asarray(true_lens, jnp.int32)[:, None])  # (B,S)
        a = jnp.where(valid[..., None], a, 0.0)
        xv = jnp.where(valid[..., None, None], xv, 0.0)

    if cache is not None and S == 1:
        # streaming decode: single recurrence step
        state = cache["ssm"].astype(jnp.float32)             # (B,H,N,P)
        bh = jnp.repeat(b, h // g, axis=2)[:, 0]             # (B,H,N)
        chh = jnp.repeat(c, h // g, axis=2)[:, 0]
        state = (jnp.exp(a[:, 0])[..., None, None] * state
                 + jnp.einsum("bhn,bhp->bhnp", bh.astype(jnp.float32),
                              xv[:, 0]))
        y = jnp.einsum("bhn,bhnp->bhp", chh.astype(jnp.float32), state)
        y = y[:, None]                                       # (B,1,H,P)
        final = state
    else:
        init = cache["ssm"] if cache is not None else None
        y, final = ssd_chunked(xv, a, b, c, chunk=chunk, initial_state=init)

    y = y + p["d_skip"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, di).astype(x.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z))
    out = dense(p, "out_proj", y, adapters=adapters, peft=peft)
    new_cache = {"conv": new_conv.astype(x.dtype),
                 "ssm": final.astype(jnp.float32)}
    return out, new_cache
