"""The benchmark's own side of a model: weights and tenant adapters made
from the seed, and the plain reference that decides ``correct``.

Nothing here imports the system under test.  The weights are laid out in
the tree the system's backbone reads (stacked per layer, one ``pos0``
pattern unit), so the harness hands the same arrays to the system and
to the reference.

The reference is a straightforward float32 forward pass of the block the
configuration file states: pre-norm RMSNorm, rotary attention, a GELU
two-matrix or SwiGLU MLP, and ETHER reflections of the inputs of every
targeted projection (paper Eq. 3: H = I - 2 u u^T / |u|^2, one block per
n-th of the input width).  It runs layer by layer under a scan, casting
each layer's bf16 weights to float32 inside, so the whole model never
exists in float32 at once.

``quant`` gives a control, the same forward computed one precision below
the configuration's (the mix file names it): ``fp8`` or ``int8``, the
steps below bf16, take every projection as a product of e4m3 or int8
values, activations scaled per token and weights per output channel;
``bfloat16``, the step below float32, takes every projection in bf16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MIXER = ("q_proj", "k_proj", "v_proj", "o_proj")


def key(seed: int, *tags: int) -> jax.Array:
    """PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(0)
    for part in (seed & 0xFFFFFFFF, seed >> 32, *tags):
        k = jax.random.fold_in(k, np.uint32(part))
    return k


def dims(cfg: dict) -> dict:
    """The sizes the reference and the counts read from a config file."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        L=cfg["num_hidden_layers"], d=d, H=h,
        Hkv=cfg.get("num_key_value_heads") or h, hd=d // h,
        ff=cfg["intermediate_size"], V=cfg["vocab_size"],
        glu=not cfg["hidden_act"].startswith("gelu"),
        tied=bool(cfg["tie_word_embeddings"]),
        eps=float(cfg.get("rms_norm_eps", cfg.get("layer_norm_eps"))),
        theta=float(cfg["rope_theta"]))


def kernel_shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    """(d_in, d_out) of every per-layer projection."""
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    out = {"q_proj": (d, m["H"] * hd), "k_proj": (d, m["Hkv"] * hd),
           "v_proj": (d, m["Hkv"] * hd), "o_proj": (m["H"] * hd, d),
           "up_proj": (d, m["ff"]), "down_proj": (m["ff"], d)}
    if m["glu"]:
        out["gate_proj"] = (d, m["ff"])
    return out


def _group(name: str) -> str:
    return "mixer" if name in MIXER else "mlp"


def targets(cfg: dict) -> tuple[str, ...]:
    return tuple(t for t in cfg["adapter"]["targets"]
                 if t in kernel_shapes(cfg))


def n_blocks(cfg: dict, d_in: int) -> int:
    """Largest divisor of d_in not above the configured block count."""
    n = min(cfg["adapter"]["n_blocks"], d_in)
    while d_in % n:
        n -= 1
    return n


def make_weights(cfg: dict, seed: int):
    """Random bf16 weights in the system's tree, in one jitted call on
    the device: lecun-normal projections, N(0, 0.02) embeddings, unit
    norm scales."""
    m = dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    shapes = kernel_shapes(cfg)

    def build(k):
        ks = jax.random.split(k, len(shapes) + 2)
        layer = {"norm1": {"scale": jnp.ones((m["L"], m["d"]), dt)},
                 "norm2": {"scale": jnp.ones((m["L"], m["d"]), dt)},
                 "mixer": {}, "mlp": {}}
        for kk, (name, (di, do)) in zip(ks[2:], sorted(shapes.items())):
            w = jax.random.normal(kk, (m["L"], di, do), dt)
            layer[_group(name)][name] = {
                "kernel": w * jnp.asarray(1.0 / math.sqrt(di), dt)}
        tree = {"embed": {"table": jax.random.normal(
                    ks[0], (m["V"], m["d"]), dt) * jnp.asarray(0.02, dt)},
                "final_norm": {"scale": jnp.ones((m["d"],), dt)},
                "units": {"pos0": layer}}
        if not m["tied"]:
            tree["lm_head"] = {"kernel": jax.random.normal(
                ks[1], (m["d"], m["V"]), dt)
                * jnp.asarray(1.0 / math.sqrt(m["d"]), dt)}
        return tree

    return jax.jit(build)(key(seed, 1))


def adapter_fn(cfg: dict, seed: int):
    """``tid -> adapter tree`` for the tenant universe: ETHER hyperplanes
    u ~ N(0, 1) per block (the paper's random start, distance 2 from the
    identity), float32, one jitted program for every tenant."""
    m = dims(cfg)
    shapes = kernel_shapes(cfg)
    dt = jnp.dtype(cfg["adapter"]["dtype"])
    names = targets(cfg)
    base = key(seed, 2)

    @jax.jit
    def build(tid):
        k = jax.random.fold_in(base, tid)
        out = {"mixer": {}, "mlp": {}}
        for i, name in enumerate(names):
            di = shapes[name][0]
            n = n_blocks(cfg, di)
            out[_group(name)][name] = {"u": jax.random.normal(
                jax.random.fold_in(k, i), (m["L"], n, di // n), dt)}
        return {"units": {"pos0": {g: v for g, v in out.items() if v}}}

    return lambda tid: build(jnp.uint32(tid))


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotary embedding over the whole head, halves rotated: x (T, H, D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _reflect(x, u):
    """ETHER: each n-th of x reflected in the hyperplane normal to u_i."""
    n, db = u.shape
    uh = u / jnp.linalg.norm(u, axis=-1, keepdims=True)
    xb = x.reshape(*x.shape[:-1], n, db)
    xb = xb - 2.0 * jnp.sum(xb * uh, -1, keepdims=True) * uh
    return xb.reshape(x.shape)


def _q8(a, axis):
    """Symmetric int8 values (as float32) times their scale along
    ``axis``; the gradient passes straight through the rounding."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(a / s), -127, 127) * s
    return a + jax.lax.stop_gradient(q - a)


def _f8(a, axis):
    """float8 e4m3 values (as float32) times their scale along ``axis``:
    4 significant bits, largest 448, subnormal below 2^-6 in steps of
    2^-9; the gradient passes straight through the rounding."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    y = a / s
    m, e = jnp.frexp(y)
    q = jnp.where(jnp.abs(y) < 2.0 ** -6, jnp.round(y * 512.0) / 512.0,
                  jnp.ldexp(jnp.round(m * 16.0) / 16.0, e))
    return a + jax.lax.stop_gradient(q * s - a)


def _matmul(x, w, quant):
    if quant is None:
        return x @ w
    if quant == "bfloat16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if quant == "fp8":
        return _f8(x, -1) @ _f8(w, 0)
    return _q8(x, -1) @ _q8(w, 0)


def _head(cfg, weights):
    f32 = jnp.float32
    if dims(cfg)["tied"]:
        return weights["embed"]["table"].astype(f32).T
    return weights["lm_head"]["kernel"].astype(f32)


def reference_hidden(cfg: dict, weights, adapter, tokens, quant=None,
                     remat=False):
    """float32 final hidden states (B, T, d) of token rows (B, T), causal:
    a row depends only on the tokens up to it, so padding at the end is
    free.  ``remat`` recomputes each layer in the backward pass."""
    m = dims(cfg)
    f32 = jnp.float32
    B, T = tokens.shape
    pos = jnp.arange(T, dtype=f32)
    mask = jnp.tril(jnp.ones((T, T), bool))
    rep = m["H"] // m["Hkv"]

    def proj(lw, la, name, x):
        w = lw[_group(name)][name]["kernel"].astype(f32)
        if name in la.get(_group(name), {}):
            x = _reflect(x, la[_group(name)][name]["u"].astype(f32))
        return _matmul(x, w, quant)

    def layer(x, xs):
        lw, la = xs
        h = _rmsnorm(x, lw["norm1"]["scale"].astype(f32), m["eps"])
        q = proj(lw, la, "q_proj", h).reshape(B, T, m["H"], m["hd"])
        k = proj(lw, la, "k_proj", h).reshape(B, T, m["Hkv"], m["hd"])
        v = proj(lw, la, "v_proj", h).reshape(B, T, m["Hkv"], m["hd"])
        q = _rope(q, pos, m["theta"])
        k = _rope(k, pos, m["theta"])
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(m["hd"])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhts,bshd->bthd", p, v).reshape(B, T, -1)
        x = x + proj(lw, la, "o_proj", a)
        h = _rmsnorm(x, lw["norm2"]["scale"].astype(f32), m["eps"])
        if m["glu"]:
            g = proj(lw, la, "gate_proj", h)
            z = g * jax.nn.sigmoid(g) * proj(lw, la, "up_proj", h)
        else:
            z = proj(lw, la, "up_proj", h)
            z = 0.5 * z * (1.0 + jnp.tanh(
                math.sqrt(2.0 / math.pi) * (z + 0.044715 * z ** 3)))
        return x + proj(lw, la, "down_proj", z), None

    if remat:
        layer = jax.checkpoint(layer)
    x = weights["embed"]["table"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, (weights["units"]["pos0"],
                                   adapter["units"]["pos0"]))
    return _rmsnorm(x, weights["final_norm"]["scale"].astype(f32), m["eps"])


def reference_logits(cfg: dict, weights, adapter, tokens, quant=None):
    """float32 logits (T, V) of one token sequence (T,)."""
    h = reference_hidden(cfg, weights, adapter, tokens[None], quant)[0]
    return _matmul(h, _head(cfg, weights), quant)


def reference_loss(cfg: dict, weights, adapter, tokens, labels, quant=None,
                   rows=None):
    """Mean next-token cross-entropy over rows (B, T) of tokens and
    labels, the output head applied one row at a time.  ``rows`` keeps
    only the first that many rows (a fault: part of the batch left out)."""
    if rows is not None:
        tokens, labels = tokens[:rows], labels[:rows]
    h = reference_hidden(cfg, weights, adapter, tokens, quant, remat=True)
    head = _head(cfg, weights)

    @jax.checkpoint
    def row(args):
        hb, yb = args
        logits = _matmul(hb, head, quant)
        gold = jnp.take_along_axis(logits, yb[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    return jnp.sum(jax.lax.map(row, (h, labels))) / labels.size


def adamw_step(opt: dict, adapter, mu, nu, count, grads):
    """One AdamW step as the mix file states it: clip by global norm,
    Adam moments with bias correction, constant learning rate, no
    weight decay.  Returns (clipped grads, adapter, mu, nu, count)."""
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, opt["clip"] / (norm + 1e-9)), grads)
    count = count + 1
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree_util.tree_map(lambda m_, x: b1 * m_ + (1 - b1) * x, mu, g)
    nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    adapter = jax.tree_util.tree_map(
        lambda p, m_, v: p - opt["lr"] * (m_ / c1)
        / (jnp.sqrt(v / c2) + opt["eps"]), adapter, mu, nu)
    return g, adapter, mu, nu, count


def train_steps(cfg: dict, opt: dict, quant=None, rows=None):
    """Jitted ``(weights, adapter, mu, nu, count, tokens, labels) ->
    (loss, clipped grads, adapter, mu, nu, count)``: one reference
    finetuning step, under full float32 precision."""
    def fn(weights, adapter, mu, nu, count, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(
                lambda a: reference_loss(cfg, weights, a, tokens, labels,
                                         quant, rows))(adapter)
        g, adapter, mu, nu, count = adamw_step(opt, adapter, mu, nu, count,
                                               grads)
        return loss, g, adapter, mu, nu, count
    return jax.jit(fn)


def served_gaps(cfg: dict, quant=None):
    """Jitted ``(weights, adapter, tokens (T,), served (G,), pos (G,))
    -> (gap (G,), pick (G,))``: at each position ``pos[i]`` (the row whose
    logits chose served token i), how far the picked token's reference
    logit lies below the reference's best.  The pick is the served token;
    with ``quant`` it is the control's own first choice instead."""
    def fn(weights, adapter, tokens, served, pos):
        with jax.default_matmul_precision("highest"):
            ref = reference_logits(cfg, weights, adapter, tokens)[pos]
            pick = served
            if quant is not None:
                ctl = reference_logits(cfg, weights, adapter, tokens, quant)
                pick = jnp.argmax(ctl[pos], -1).astype(served.dtype)
        got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return jnp.max(ref, -1) - got, pick

    return jax.jit(fn)
