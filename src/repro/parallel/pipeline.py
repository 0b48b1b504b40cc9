"""Pipeline parallelism (optional runtime): GPipe-style microbatched
stage pipeline on shard_map + ppermute.

Scope (DESIGN.md §4): the production meshes here use DP/FSDP × TP(+EP) —
for PEFT finetuning there is no optimizer-state memory pressure, so
scan-over-layers + FSDP covers the memory story without pipeline
bubbles. This module exists for the full-finetune/pretraining regime and
as the compiled-tested building block for a `pp` mesh axis.

Model contract: the network is a chain of S stage functions with
identical (B_micro, ...) -> (B_micro, ...) activation signatures; stage
s's parameters live on pipeline rank s (sharded over the ``stage`` mesh
axis). The schedule runs M microbatches through S stages in S+M−1 ticks
(GPipe); each tick every rank computes its resident microbatch and
ppermutes the activations forward.

    y = pipeline_apply(stage_fn, stage_params, x, mesh, n_micro=M,
                       stage_axis="stage")
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   mesh, *, n_micro: int, stage_axis: str = "stage"):
    """Run x (B, ...) through S = mesh.shape[stage_axis] stages.

    stage_params: pytree whose leaves have a leading S dim (stage-major).
    stage_fn(params_slice, h, stage_index) -> h. B % n_micro == 0.
    """
    S = mesh.shape[stage_axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro

    def body(params_local, x_local):
        # params_local: stage slice (1, ...) on this rank; x_local: the
        # full batch replicated along the stage axis (inputs are cheap;
        # a production variant feeds rank 0 only).
        params_me = jax.tree_util.tree_map(lambda a: a[0], params_local)
        rank = jax.lax.axis_index(stage_axis)
        micro = x_local.reshape(n_micro, mb, *x_local.shape[1:])
        n_ticks = S + n_micro - 1

        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            buf, outs = carry            # buf: activation resident here
            # which microbatch is at this rank at tick t: m = t - rank
            m = t - rank
            active = (m >= 0) & (m < n_micro)
            # rank 0 ingests microbatch m at tick t
            inject = jnp.where(m >= 0, jnp.clip(m, 0, n_micro - 1), 0)
            h_in = jnp.where(rank == 0, micro[inject], buf)
            h_out = stage_fn(params_me, h_in, rank)
            h_out = jnp.where(active, h_out, buf)
            # last stage banks its finished microbatch
            done = active & (rank == S - 1)
            outs = jax.lax.cond(
                done,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, h_out[None], jnp.clip(m, 0, n_micro - 1), axis=0),
                lambda o: o, outs)
            # shift activations to the next stage
            buf = jax.lax.ppermute(h_out, stage_axis, perm)
            return (buf, outs), ()

        buf0 = jnp.zeros_like(micro[0])
        outs0 = jnp.zeros_like(micro)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(n_ticks))
        # outs is populated only on the last rank; broadcast via psum of
        # the masked buffer (ppermute can't fan out 1→S)
        outs = jax.lax.psum(
            jnp.where(rank == S - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs.reshape(B, *x_local.shape[1:])

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x)
