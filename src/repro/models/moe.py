"""Mixture-of-Experts MLP with sort-based capacity dispatch (EP-ready).

Dispatch strategy (MaxText-style, deterministic, no host control flow):
tokens are ranked inside their assigned expert via a stable sort of the
flat expert ids; each expert owns a fixed-capacity (E, C, d) buffer —
overflow tokens are dropped (capacity_factor controls slack). Everything
is jnp (sort / scatter / batched matmul), so under pjit the dispatch
lowers to XLA collectives when the token and expert dims live on
different mesh axes (EP over "model", tokens over "data"/"pod").

ETHER on experts: adapters are stacked per-expert, shard with the expert
axis, and are applied inside the vmapped expert MLP — per-expert
hyperplane reflections (DESIGN.md §5).  The execution backend rides in
``peft.backend`` (DESIGN.md §3): Pallas kernels are vmap-safe (the
batching rule prepends grid dims), so expert MLPs can hit the fused
reflect-GEMM when capacity/d_ff tile.  Per-*tenant* AdapterBank serving
is not available inside experts — capacity dispatch destroys the batch
dim the bank gather keys on (adapted_dense raises).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.peft import get_adapter
from repro.models.layers import ACTS, init_dense
from repro.parallel.context import shard_moe_buffer

Params = dict[str, Any]


def init_moe(rng, d_model: int, d_ff: int, n_experts: int, dtype) -> Params:
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    e = (n_experts,)
    return {
        "router": init_dense(k0, d_model, n_experts, dtype),
        "gate_proj": init_dense(k1, d_model, d_ff, dtype, stack=e),
        "up_proj": init_dense(k2, d_model, d_ff, dtype, stack=e),
        "down_proj": init_dense(k3, d_ff, d_model, dtype, stack=e),
    }


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def moe_mlp(p: Params, x: jax.Array, *, top_k: int, n_experts: int,
            capacity_factor: float = 1.25, act: str = "silu",
            adapters=None, peft=None):
    """x: (B, S, d). Returns (y, aux_metrics).

    aux_metrics: {"aux_loss": load-balance loss, "router_z": z-loss}.
    On (dp × model) meshes with E % model == 0 this routes through the
    shard_map all-to-all dispatch (§Perf A1 — moe_a2a.py); the portable
    jnp path below is the single-device / fallback implementation.
    """
    from repro.parallel.context import get_context
    B, S, d = x.shape
    ctx = get_context()
    if (ctx is not None and ctx.moe_a2a and ctx.model_size > 1
            and n_experts % ctx.model_size == 0
            and S % ctx.model_size == 0):
        from repro.models.moe_a2a import moe_mlp_a2a
        return moe_mlp_a2a(p, x, top_k=top_k, n_experts=n_experts,
                           ctx=ctx, capacity_factor=capacity_factor,
                           act=act, adapters=adapters, peft=peft)
    N = B * S
    E, K = n_experts, top_k
    xf = x.reshape(N, d)

    logits = (xf @ p["router"]["kernel"].astype(xf.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (N, E)
    gates, ids = jax.lax.top_k(probs, K)                       # (N, K)
    gates = gates / jnp.sum(gates, -1, keepdims=True)          # renorm

    # --- aux losses (Switch-style) ---
    me = jnp.mean(probs, axis=0)                               # mean prob
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=1), axis=0
    ) / K                                                      # mean load
    aux_loss = E * jnp.sum(me * ce)
    router_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # --- sort-based dispatch with fixed capacity ---
    C = _round_up(max(int(N * K * capacity_factor / E), 1), 8)
    flat_ids = ids.reshape(-1)                                 # (N·K,)
    flat_gates = gates.reshape(-1).astype(jnp.float32)
    order = jnp.argsort(flat_ids, stable=True)                 # (N·K,)
    sorted_ids = flat_ids[order]
    counts = jnp.bincount(flat_ids, length=E)
    starts = jnp.cumsum(counts) - counts                       # (E,)
    ranks = jnp.arange(N * K, dtype=jnp.int32) - starts[sorted_ids]
    keep = ranks < C
    slot = sorted_ids * C + jnp.clip(ranks, 0, C - 1)
    slot = jnp.where(keep, slot, E * C)                        # junk row
    tok = order // K                                           # source token

    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xf[tok])
    buf = shard_moe_buffer(buf[:E * C].reshape(E, C, d))

    # --- per-expert MLP (vmapped; ETHER adapters ride along) ---
    def expert_fn(kg, ku, kd, ag, au, ad, xe):
        from repro.core.transforms import adapted_dense
        with jax.named_scope("gate_proj"):
            g = adapted_dense(xe, kg, None, ag, peft)
        with jax.named_scope("up_proj"):
            u = adapted_dense(xe, ku, None, au, peft)
        h = ACTS[act](g) * u
        with jax.named_scope("down_proj"):
            return adapted_dense(h, kd, None, ad, peft)

    ag = get_adapter(adapters, "gate_proj")
    au = get_adapter(adapters, "up_proj")
    ad = get_adapter(adapters, "down_proj")
    none_axes = None
    in_axes = (0, 0, 0,
               none_axes if ag is None else 0,
               none_axes if au is None else 0,
               none_axes if ad is None else 0, 0)
    y_ec = jax.vmap(expert_fn, in_axes=in_axes)(
        p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"], ag, au, ad, buf)             # (E, C, d)

    # --- combine (weighted scatter-add back to tokens) ---
    y_flat = jnp.concatenate(
        [y_ec.reshape(E * C, d),
         jnp.zeros((1, d), y_ec.dtype)], axis=0)               # junk row
    contrib = y_flat[slot].astype(jnp.float32) * \
        jnp.where(keep, flat_gates[order], 0.0)[:, None]
    y = jnp.zeros((N, d), jnp.float32).at[tok].add(contrib)
    return (y.reshape(B, S, d).astype(x.dtype),
            {"aux_loss": aux_loss, "router_z": router_z,
             "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))})
