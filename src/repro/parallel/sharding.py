"""Logical sharding rules: (leaf path, shape, mesh) → PartitionSpec.

One rules table covers params, adapters, optimizer states (their leaf
paths end with the same module/kernel names), KV/SSM caches, and input
batches, across every architecture in the zoo.  Scheme (DESIGN.md §4):

* FSDP over the data axes (``("pod","data")`` when multi-pod) on the
  weight dim that matches the activation contraction;
* TP over ``model`` on heads / d_ff / vocab (flattened head dims, so
  GQA KV projections shard evenly even when n_kv < model parallelism);
* EP: MoE expert banks (and their per-expert ETHER adapters) put the
  expert dim on ``model``;
* adapters are replicated by default — they are the ~0.01% trainable
  fraction, and replication makes their DP gradient all-reduce the only
  cross-pod traffic in PEFT training;
* caches: batch→dp; KV heads→model when divisible, else head_dim→model;
* batch arrays: leading batch dim → dp (skipped when B == 1, e.g.
  long_500k, instead of padding a 16× waste).

Rules are *functions of shape*, so a checkpoint written on one mesh can
be restored onto any other (runtime/elastic.py).
"""

from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.pytree import map_with_paths


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _sizes(mesh: Mesh):
    dpx = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dpx])) if dpx else 1
    model = mesh.shape.get("model", 1)
    return dpx, dp_size, model


# ---------------------------------------------------------------------------
# Parameter / optimizer-state rules
# ---------------------------------------------------------------------------

_IN_PROJ = re.compile(
    r"(q_proj|k_proj|v_proj|gate_proj|up_proj|in_proj|in_x|in_y|mm_proj/up_proj"
    r"|router)/kernel$")
_OUT_PROJ = re.compile(
    r"(o_proj|down_proj|out_proj|mm_proj/down_proj)/kernel$")
_EXPERT = re.compile(r"(gate_proj|up_proj|down_proj)/kernel$")


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def _pick(shape: tuple[int, ...], candidates, mesh: Mesh) -> P:
    """First candidate (right-aligned spec tuple) where every sharded
    dim is divisible by its axis size — pjit rejects uneven shardings."""
    nd = len(shape)
    for cand in candidates:
        cand = cand[-nd:] if len(cand) > nd else cand
        dims = shape[nd - len(cand):]
        if all(d % _axis_size(mesh, e) == 0 for d, e in zip(dims, cand)):
            return P(*([None] * (nd - len(cand)) + list(cand)))
    return P()


def spec_for_param(path: str, shape: tuple[int, ...], mesh: Mesh,
                   serve: bool = False) -> P:
    """PartitionSpec for a parameter-like leaf (params, adapter, opt
    moments — the trailing path components decide). Every rule is a
    preference list; the first divisibility-satisfying layout wins.

    ``serve=True`` (§Perf D): drop FSDP — weights shard over ``model``
    only and replicate across dp, so decode never all-gathers the model
    per token. Exception: 4-D MoE expert banks keep dp sharding (a 235B
    expert bank does not fit per-chip under EP alone)."""
    dpx, dp_size, model = _sizes(mesh)
    nd = len(shape)
    dp = dpx if dpx else None
    if serve and nd < 4:
        dp = None

    def pick(*cands):
        return _pick(shape, cands, mesh)

    if nd == 0 or (not dpx and model == 1):
        return P()
    if path.endswith("embed/table"):                 # (V, d)
        return pick(("model", dp), (None, dp), (None, "model"))
    if path.endswith("pos_embed"):                   # (T, d)
        return pick((None, dp), (None, "model"))
    if path.endswith("lm_head/kernel"):              # (d, V)
        return pick((dp, "model"), (None, "model"), (dp, None))
    # MoE expert banks: (L, E, d_in, d_out) — expert dim on model (EP)
    if _EXPERT.search(path) and nd == 4 and not path.startswith("rem"):
        if "down_proj" in path:
            return pick((None, "model", None, dp), (None, "model", None, None))
        return pick((None, "model", dp, None), (None, "model", None, None))
    if "gate_a/kernel" in path or "gate_x/kernel" in path:
        return pick(("model", None, None))           # (.., H, hd, hd)
    if path.endswith("conv/kernel"):
        return pick((None, "model"))                 # (.., W, C)
    if path.endswith("conv/bias"):
        return pick(("model",))
    if _OUT_PROJ.search(path):                       # (.., d_proj, d)
        return pick(("model", dp), ("model", None), (None, dp))
    if _IN_PROJ.search(path):                        # (.., d, d_proj)
        return pick((dp, "model"), (None, "model"), (dp, None))
    if path.endswith("/lam") or path.endswith("a_log") \
            or path.endswith("dt_bias") or path.endswith("d_skip"):
        return P()
    # adapters: replicate, except per-expert stacks (L, E, n, db) which
    # co-locate with the EP axis
    if re.search(r"/(u|u1|v1|u2|v2|a|b|r|m|d_vec|b_vec|seed)$", path):
        if nd == 4:
            return pick((None, "model", None, None))
        return P()
    if path.endswith("kernel") and nd >= 2:          # generic dense
        return pick((dp, "model"), (None, "model"), (dp, None))
    return P()                                       # norms, biases, scalars


# ---------------------------------------------------------------------------
# Cache rules
# ---------------------------------------------------------------------------

def spec_for_cache(path: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    dpx, dp_size, model = _sizes(mesh)
    nd = len(shape)
    dp = dpx if dpx else None

    def tail(*spec):
        return P(*([None] * (nd - len(spec)) + list(spec)))

    if nd == 0:
        return P()
    base = path.rsplit("/", 1)[-1]
    if base in ("k", "v"):                           # (.., B, kv, T, hd)
        b, kv, t, hd = shape[-4:]
        bspec = dp if b % max(dp_size, 1) == 0 and b > 1 else None
        if kv % model == 0:
            return tail(bspec, "model", None, None)
        if t % model == 0:
            # §Perf D2: T-sharded cache — decode attends via partial
            # logits + tiny softmax psums instead of gathering the
            # hd-sharded cache per layer (10.7→<1 GB/chip temps).
            return tail(bspec, None, "model", None)
        if hd % model == 0:
            return tail(bspec, None, None, "model")
        return tail(bspec, None, None, None)
    if base == "ssm":                                # (.., B, H, N, P)
        b, h = shape[-4], shape[-3]
        bspec = dp if b % max(dp_size, 1) == 0 and b > 1 else None
        hspec = "model" if h % model == 0 else None
        return tail(bspec, hspec, None, None)
    if base == "conv":                               # (.., B, W-1, C)
        b, _, c = shape[-3:]
        bspec = dp if b % max(dp_size, 1) == 0 and b > 1 else None
        cspec = "model" if c % model == 0 else None
        return tail(bspec, None, cspec)
    if base == "h":                                  # (.., B, D)
        b, d = shape[-2:]
        bspec = dp if b % max(dp_size, 1) == 0 and b > 1 else None
        dspec = "model" if d % model == 0 else None
        return tail(bspec, dspec)
    return P()


# ---------------------------------------------------------------------------
# Batch rules
# ---------------------------------------------------------------------------

def spec_for_batch(path: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    dpx, dp_size, _ = _sizes(mesh)
    nd = len(shape)
    if nd == 0 or not dpx:
        return P()
    b = shape[0]
    bspec = dpx if b % dp_size == 0 and b > 1 else None
    return P(*([bspec] + [None] * (nd - 1)))


# ---------------------------------------------------------------------------
# Tree-level helpers
# ---------------------------------------------------------------------------

def _tree_specs(tree: Any, mesh: Mesh, rule) -> Any:
    return map_with_paths(lambda p, l: rule(p, tuple(l.shape), mesh), tree)


def param_specs(tree, mesh, serve: bool = False):
    return _tree_specs(
        tree, mesh,
        lambda p, s, m: spec_for_param(p, s, m, serve=serve))


def cache_specs(tree, mesh):
    return _tree_specs(tree, mesh, spec_for_cache)


def batch_specs(tree, mesh):
    return _tree_specs(tree, mesh, spec_for_batch)


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
