"""The benchmark's own side of a model, shared by every architecture:
tenant adapters made from the seed, the pieces a plain reference is
built from, and the reference programs that decide ``correct``.

Nothing here imports the system under test, and nothing here knows a
block: what a configuration's block is (its sizes, its projections, its
weights, its forward pass) is its architecture module's,
``bench/archs/<arch>.py``, found by ``harness.arch``.  The weights are
laid out in the tree the system's backbone reads, so the harness hands
the same arrays to the system and to the reference.

The reference is a straightforward float32 forward pass, under
``highest`` precision, with ETHER reflections of the inputs of every
targeted projection (paper Eq. 3: H = I - 2 u u^T / |u|^2, one block per
n-th of the input width).  An architecture runs it layer by layer,
casting each layer's bf16 weights to float32 inside, so the whole model
never exists in float32 at once.

``quant`` gives a control, the same forward computed one precision below
the configuration's (the mix file names it): ``fp8`` or ``int8``, the
steps below bf16, take every projection as a product of e4m3 or int8
values, activations scaled per token and weights per output channel;
``bfloat16``, the step below float32, takes every projection in bf16.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness


class Unit(NamedTuple):
    """One pattern position of a stack: where its layers' leaves sit in
    the weight tree (``units/pos0``), how many layers it stacks, and
    ``(d_in, d_out)`` of each of its projections by path under the unit
    (``mixer/q_proj``)."""
    where: str
    count: int
    shapes: dict


def key(seed: int, *tags: int) -> jax.Array:
    """PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(0)
    for part in (seed & 0xFFFFFFFF, seed >> 32, *tags):
        k = jax.random.fold_in(k, np.uint32(part))
    return k


def targets(cfg: dict, shapes: dict) -> tuple[str, ...]:
    """The paths of ``shapes`` that the adapter targets, in the order of
    the configuration's ``targets``."""
    return tuple(p for t in cfg["adapter"]["targets"] for p in shapes
                 if p.rsplit("/", 1)[-1] == t)


def n_blocks(cfg: dict, d_in: int) -> int:
    """Largest divisor of d_in not above the configured block count."""
    n = min(cfg["adapter"]["n_blocks"], d_in)
    while d_in % n:
        n -= 1
    return n


def _put(tree: dict, path: str, leaf) -> None:
    *parents, last = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[last] = leaf


def adapter_fn(cfg: dict, seed: int):
    """``tid -> adapter tree`` for the tenant universe: ETHER hyperplanes
    u ~ N(0, 1) per block (the paper's random start, distance 2 from the
    identity), float32, one jitted program for every tenant.  The i-th
    targeted projection, counted over the units in order, takes the
    tenant's key folded with i."""
    units = harness.arch(cfg).projections(cfg)
    dt = jnp.dtype(cfg["adapter"]["dtype"])
    base = key(seed, 2)

    @jax.jit
    def build(tid):
        k = jax.random.fold_in(base, tid)
        out: dict = {}
        i = 0
        for u in units:
            for p in targets(cfg, u.shapes):
                di = u.shapes[p][0]
                n = n_blocks(cfg, di)
                _put(out, f"{u.where}/{p}/u", jax.random.normal(
                    jax.random.fold_in(k, i), (u.count, n, di // n), dt))
                i += 1
        return out

    return lambda tid: build(jnp.uint32(tid))


# ---------------------------------------------------------------------------
# Pieces of a reference forward
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


def project(lw, la, path: str, x, quant):
    """x through one layer's projection at ``path`` (``mixer/q_proj``),
    its input reflected first where the layer's adapters hold it."""
    group, name = path.split("/")
    w = lw[group][name]["kernel"].astype(jnp.float32)
    if name in la.get(group, {}):
        x = _reflect(x, la[group][name]["u"].astype(jnp.float32))
    return _matmul(x, w, quant)


def attention(lw, la, h, pos, mask, heads: int, kv_heads: int, theta,
              quant):
    """Causal grouped-query self-attention of the program's ``mixer``
    tree (q, k, v and o projections, no biases): h (B, T, d) to the
    o projection's output.  ``theta`` is the rotary base over whole
    heads, or None for no position embedding; ``mask`` (T, T) the
    causal mask."""
    B, T, _ = h.shape
    q = project(lw, la, "mixer/q_proj", h, quant).reshape(B, T, heads, -1)
    k = project(lw, la, "mixer/k_proj", h, quant).reshape(B, T, kv_heads, -1)
    v = project(lw, la, "mixer/v_proj", h, quant).reshape(B, T, kv_heads, -1)
    if theta is not None:
        q = _rope(q, pos, theta)
        k = _rope(k, pos, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhts,bshd->bthd", p, v).reshape(B, T, -1)
    return project(lw, la, "mixer/o_proj", a, quant)


def mlp(lw, la, h, glu: bool, quant):
    """The program's ``mlp`` tree: SwiGLU (gate, up, down) where ``glu``,
    else a two-matrix MLP with the tanh GELU."""
    if glu:
        g = project(lw, la, "mlp/gate_proj", h, quant)
        z = g * jax.nn.sigmoid(g) * project(lw, la, "mlp/up_proj", h, quant)
    else:
        z = project(lw, la, "mlp/up_proj", h, quant)
        z = 0.5 * z * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (z + 0.044715 * z ** 3)))
    return project(lw, la, "mlp/down_proj", z, quant)


def _head(weights):
    """The output head's matrix (d, V): the untied head where the tree
    has one, else the embedding table."""
    if "lm_head" in weights:
        return weights["lm_head"]["kernel"].astype(jnp.float32)
    return weights["embed"]["table"].astype(jnp.float32).T


# ---------------------------------------------------------------------------
# Reference programs
# ---------------------------------------------------------------------------

def reference_logits(cfg: dict, weights, adapter, tokens, quant=None):
    """float32 logits (T, V) of one token sequence (T,)."""
    h = harness.arch(cfg).reference_hidden(cfg, weights, adapter,
                                           tokens[None], quant)[0]
    return _matmul(h, _head(weights), quant)


def reference_loss(cfg: dict, weights, adapter, tokens, labels, quant=None,
                   rows=None):
    """Mean next-token cross-entropy over rows (B, T) of tokens and
    labels, the output head applied one row at a time.  ``rows`` keeps
    only the first that many rows (a fault: part of the batch left out)."""
    if rows is not None:
        tokens, labels = tokens[:rows], labels[:rows]
    h = harness.arch(cfg).reference_hidden(cfg, weights, adapter, tokens,
                                           quant, remat=True)
    head = _head(weights)

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
