"""Expert-parallel MoE dispatch via shard_map + all-to-all (§Perf A1).

Why: the portable jnp dispatch in moe.py is correct but GSPMD partitions
its global argsort/scatter/gather as *all-reduces of the entire dispatch
buffer* — measured 77 TB/chip/step on qwen3-moe train_4k (see
EXPERIMENTS.md §Perf). The physical movement an MoE layer needs is one
all-to-all of the routed tokens (~300 MB/chip/layer); this module says
so explicitly with shard_map.

Topology: tokens live on (dp × model)-sharded (B, S) — each of the
M = |model| shards owns E/M experts. Routing is computed locally; tokens
are bucketed by destination shard (capacity C_s), exchanged with ONE
all-to-all, locally sub-dispatched to the owning expert (capacity C2),
computed, and returned with a second all-to-all; gating/combination
happens back at the source shard. Both sorts are shard-local.

Everything is differentiable (all_to_all transposes to all_to_all), so
the same path serves ETHER-PEFT training; per-expert ETHER adapters ride
along with the model-sharded expert banks.  As in moe.py, the execution
backend (jnp / pallas / auto) rides in ``peft.backend`` and dispatches
inside adapted_dense — shard_map-local expert GEMMs included.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core.peft import get_adapter
from repro.models.layers import ACTS
from repro.parallel.context import MeshContext

Params = dict[str, Any]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _local_dispatch(flat_ids, n_buckets: int, capacity: int):
    """Shard-local capacity dispatch: (slot, keep, order) for scattering
    items into (n_buckets, capacity). All ops local (no collectives)."""
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    counts = jnp.bincount(flat_ids, length=n_buckets)
    starts = jnp.cumsum(counts) - counts
    ranks = jnp.arange(flat_ids.shape[0], dtype=jnp.int32) \
        - starts[sorted_ids]
    keep = ranks < capacity
    slot = sorted_ids * capacity + jnp.clip(ranks, 0, capacity - 1)
    slot = jnp.where(keep, slot, n_buckets * capacity)     # junk row
    return slot, keep, order


def _scatter_rows(values, slot, n_rows: int):
    """values[j] → out[slot[j]] with a junk row at n_rows."""
    out = jnp.zeros((n_rows + 1, values.shape[-1]), values.dtype)
    return out.at[slot].set(values)[:n_rows]


def moe_mlp_a2a(p: Params, x: jax.Array, *, top_k: int, n_experts: int,
                ctx: MeshContext, capacity_factor: float = 1.25,
                act: str = "silu", adapters=None, peft=None):
    """Drop-in for moe.moe_mlp on (dp, model) meshes with E % M == 0.

    x: (B, S, d) sharded P(dp, "model", None). Returns (y, aux)."""
    B, S, d = x.shape
    E, K, M = n_experts, top_k, ctx.model_size
    E_l = E // M
    dp = (ctx.dp_axes if ctx.dp_axes and B % ctx.dp_size == 0 and B > 1
          else None)
    mesh = ctx.mesh
    f32 = jnp.float32

    def body(xl, wr, kg, ku, kd, ag, au, ad):
        B_l, S_l, _ = xl.shape
        N_l = B_l * S_l
        xf = xl.reshape(N_l, d)
        logits = (xf @ wr.astype(xf.dtype)).astype(f32)     # (N_l, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, K)
        gates = (gates / jnp.sum(gates, -1, keepdims=True)).astype(f32)

        # aux losses (global means via pmean over the whole mesh)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(ids, E, dtype=f32), 1),
                      axis=0) / K
        axes = tuple(mesh.axis_names)
        aux_loss = E * jnp.sum(jax.lax.pmean(me, axes)
                               * jax.lax.pmean(ce, axes))
        router_z = jax.lax.pmean(
            jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1))), axes)

        # ---- stage 1: bucket by destination shard, ONE all-to-all ----
        flat_ids = ids.reshape(-1)                          # (N_l·K,)
        dest = flat_ids // E_l
        C_s = _round_up(max(int(N_l * K / M * capacity_factor), 1), 4)
        slot, keep, order = _local_dispatch(dest, M, C_s)
        tok = order // K
        send_x = _scatter_rows(xf[tok], slot, M * C_s)      # (M·C_s, d)
        e_local = (flat_ids % E_l).astype(jnp.int32)[order]
        send_e = jnp.zeros((M * C_s + 1,), jnp.int32
                           ).at[slot].set(e_local)[:M * C_s]
        send_x = send_x.reshape(M, C_s, d)
        send_e = send_e.reshape(M, C_s)
        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0)  # (M, C_s, d)
        recv_e = jax.lax.all_to_all(send_e, "model", 0, 0)

        # ---- stage 2: local sub-dispatch to owned experts ----
        arr_x = recv_x.reshape(M * C_s, d)
        arr_e = recv_e.reshape(M * C_s)
        C2 = _round_up(max(int(M * C_s / E_l * capacity_factor), 1), 4)
        slot2, keep2, order2 = _local_dispatch(arr_e, E_l, C2)
        buf = _scatter_rows(arr_x[order2], slot2, E_l * C2)
        buf = buf.reshape(E_l, C2, d)

        def expert_fn(g, u, dn, a_g, a_u, a_d, xe):
            from repro.core.transforms import adapted_dense
            with jax.named_scope("gate_proj"):
                h = ACTS[act](adapted_dense(xe, g, None, a_g, peft))
            with jax.named_scope("up_proj"):
                h = h * adapted_dense(xe, u, None, a_u, peft)
            with jax.named_scope("down_proj"):
                return adapted_dense(h, dn, None, a_d, peft)

        y_ec = jax.vmap(expert_fn)(kg, ku, kd, ag, au, ad, buf)
        # (E_l, C2, d)

        # un-dispatch stage 2 (scatter back to arrival order)
        y_flat2 = jnp.concatenate(
            [y_ec.reshape(E_l * C2, d),
             jnp.zeros((1, d), y_ec.dtype)], 0)
        y_arr = jnp.zeros((M * C_s, d), y_ec.dtype).at[order2].set(
            y_flat2[slot2] * keep2[:, None].astype(y_ec.dtype))

        # ---- return all-to-all + combine at source ----
        ret = jax.lax.all_to_all(y_arr.reshape(M, C_s, d), "model", 0, 0)
        y_sent = jnp.concatenate(
            [ret.reshape(M * C_s, d), jnp.zeros((1, d), ret.dtype)], 0)
        contrib = y_sent[slot].astype(f32) * \
            (gates.reshape(-1)[order]
             * keep.astype(f32))[:, None]
        out = jnp.zeros((N_l, d), f32).at[tok].add(contrib)
        dropped = 1.0 - jax.lax.pmean(jnp.mean(keep.astype(f32)), axes)
        return (out.reshape(B_l, S_l, d).astype(x.dtype),
                {"aux_loss": aux_loss, "router_z": router_z,
                 "dropped_frac": dropped})

    # expert dim is the leading axis of every adapter leaf — a prefix
    # spec broadcasts over the adapter dict (empty dict = no adapters)
    ag = get_adapter(adapters, "gate_proj") or {}
    au = get_adapter(adapters, "up_proj") or {}
    ad = get_adapter(adapters, "down_proj") or {}
    a_spec = P("model")
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, "model", None),          # x
                  P(None, None),                 # router
                  P("model", None, None),        # gate bank (E, d, f)
                  P("model", None, None),        # up bank
                  P("model", None, None),        # down bank
                  a_spec, a_spec, a_spec),       # adapters (E, …)
        out_specs=(P(dp, "model", None),
                   {"aux_loss": P(), "router_z": P(),
                    "dropped_frac": P()}),
        check_vma=False)

    return fn(x, p["router"]["kernel"], p["gate_proj"]["kernel"],
              p["up_proj"]["kernel"], p["down_proj"]["kernel"],
              ag, au, ad)
