"""GQA attention with query-chunked flash semantics (pure-JAX XLA path)
and optional Pallas kernel path, causal / bidirectional / sliding-window,
KV-cache prefill & decode.

Memory strategy for long context (32k+): queries are processed in chunks
under ``jax.checkpoint`` so the peak live attention tensor is
(B, H, q_chunk, T) instead of (B, H, S, T); the backward pass recomputes
per-chunk probabilities. This is what makes `prefill_32k`/`train_4k` fit
HBM in the dry-run without a TPU-only kernel.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense, init_dense, rope

Params = dict[str, Any]
_NEG_INF = -1e30


def init_attention(rng, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   dtype, *, qkv_bias: bool = False, out_bias: bool = False
                   ) -> Params:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "q_proj": init_dense(k1, d_model, n_heads * head_dim, dtype,
                             bias=qkv_bias),
        "k_proj": init_dense(k2, d_model, n_kv * head_dim, dtype,
                             bias=qkv_bias),
        "v_proj": init_dense(k3, d_model, n_kv * head_dim, dtype,
                             bias=qkv_bias),
        "o_proj": init_dense(k4, n_heads * head_dim, d_model, dtype,
                             bias=out_bias),
    }


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1).transpose(0, 2, 1, 3)      # (B, H, S, D)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def attention_core(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: Optional[int] = None,
                   q_offset: int = 0, q_chunk: int = 512) -> jax.Array:
    """Exact attention, chunked over queries with remat (see module doc).

    q: (B, H, S, D); k/v: (B, Hkv, T, D). Returns (B, H, S, D).
    """
    from repro.parallel.context import attn_probs_dtype, get_context
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = 1.0 / (d ** 0.5)
    # §Perf C1: probs storage dtype (f32 default; bf16 halves the
    # memory-bound softmax traffic, stats stay f32)
    pdt = attn_probs_dtype(jnp.float32)
    # §Perf B1: when q fell back to sequence sharding (heads not
    # divisible by the model axis), chunks must stay multiples of the
    # shard so each chip keeps its own q rows (no per-chunk resharding).
    ctx = get_context()
    if (ctx is not None and ctx.head_shard_attn and ctx.model_size > 1
            and h % ctx.model_size != 0 and s % ctx.model_size == 0
            and s > 1):
        nc = 8 if s % (8 * ctx.model_size) == 0 else 1
        q_chunk = max(s // nc, q_chunk)

    def _one_chunk(qc: jax.Array, start: jax.Array) -> jax.Array:
        # qc: (B, H, C, D); start: scalar absolute index of first q row
        qg = qc.reshape(b, hkv, rep, -1, d)
        logits = jnp.einsum("bgrcd,bgtd->bgrct", qg.astype(pdt),
                            k.astype(pdt),
                            preferred_element_type=jnp.float32) * scale
        qpos = q_offset + start + jnp.arange(qc.shape[2])
        kpos = jnp.arange(t)
        mask = jnp.ones((qc.shape[2], t), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
        m = jnp.max(logits, axis=-1, keepdims=True)      # f32 stats
        p = jnp.exp((logits - m).astype(pdt))
        z = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
        p = (p / jnp.maximum(z, 1e-30).astype(pdt)).astype(pdt)
        out = jnp.einsum("bgrct,bgtd->bgrcd", p, v.astype(pdt),
                         preferred_element_type=jnp.float32)
        return out.reshape(b, h, -1, d).astype(q.dtype)

    if s <= q_chunk:
        return _one_chunk(q, jnp.int32(0))

    n_chunks = -(-s // q_chunk)
    pad = n_chunks * q_chunk - s
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q
    qs = qp.reshape(b, h, n_chunks, q_chunk, d).transpose(2, 0, 1, 3, 4)
    starts = jnp.arange(n_chunks, dtype=jnp.int32) * q_chunk

    chunk_fn = jax.checkpoint(_one_chunk)
    outs = jax.lax.map(lambda args: chunk_fn(*args), (qs, starts))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, h, n_chunks * q_chunk, d)
    return out[:, :, :s]


def apply_attention(p: Params, x: jax.Array, *, n_heads: int, n_kv: int,
                    head_dim: int, positions: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    rope_theta: Optional[float] = 10000.0,
                    cache: Optional[Params] = None,
                    cache_pos: Optional[jax.Array] = None,
                    q_chunk: int = 512, adapters=None, peft=None,
                    kv_x: Optional[jax.Array] = None):
    """Full attention block: projections (+adapters), RoPE, core, output.

    * train/prefill: ``cache=None`` → returns (out, new_cache_kv) where
      new_cache_kv = (k, v) for cache construction.
    * decode: ``cache={'k','v'}`` with ``cache_pos`` → writes the new
      token's KV at cache_pos, attends over the cache, returns
      (out, updated_cache).
    * cross-attention: pass ``kv_x`` (encoder states); no cache update.
    """
    q = dense(p, "q_proj", x, adapters=adapters, peft=peft)
    src = x if kv_x is None else kv_x
    k = dense(p, "k_proj", src, adapters=adapters, peft=peft)
    v = dense(p, "v_proj", src, adapters=adapters, peft=peft)
    from repro.parallel.context import shard_heads
    q = shard_heads(_split_heads(q, n_heads), "q")
    k = shard_heads(_split_heads(k, n_kv), "kv")
    v = shard_heads(_split_heads(v, n_kv), "kv")

    if rope_theta is not None:
        # positions: (B, S) for q; kv positions follow src
        q = rope(q.transpose(0, 2, 1, 3), positions, rope_theta
                 ).transpose(0, 2, 1, 3)
        if kv_x is None:
            k = rope(k.transpose(0, 2, 1, 3), positions, rope_theta
                     ).transpose(0, 2, 1, 3)

    q_offset = 0
    if cache is not None:
        # decode: write new kv at cache_pos, attend over whole cache.
        # Sliding-window layers use a ring buffer (T == window): slot
        # i holds absolute position pos − ((pos − i) mod T).  The KV
        # write and the attend run under the scope ``attn.decode``.
        with jax.named_scope("attn.decode"):
            t_cache = cache["k"].shape[2]
            ring = window is not None and t_cache == window
            write_pos = cache_pos % t_cache if ring else cache_pos
            if jnp.ndim(cache_pos) == 1:
                # Per-slot cursors (continuous batching): row b writes
                # its token at its own time index write_pos[b].
                # Advanced-index scatter; decode is single-token per
                # step by construction.
                if k.shape[2] != 1:
                    raise ValueError("per-slot cache_pos requires "
                                     "single-token decode steps")
                bidx = jnp.arange(k.shape[0])
                ck = cache["k"].at[bidx, :, write_pos, :].set(
                    k[:, :, 0, :].astype(cache["k"].dtype))
                cv = cache["v"].at[bidx, :, write_pos, :].set(
                    v[:, :, 0, :].astype(cache["v"].dtype))
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), write_pos, axis=2)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), write_pos, axis=2)
            # keep the post-scatter cache in the layout the serve engine
            # committed it with (slots→data, kv-heads/T→model) —
            # otherwise the per-token scatter would let GSPMD drift the
            # layout and the next decode step's input signature (a
            # retrace under a mesh)
            from repro.parallel.context import shard_slot_cache
            ck = shard_slot_cache(ck, "kv")
            cv = shard_slot_cache(cv, "kv")
            qpos = positions[:, -1:]                 # (B, 1) absolute pos
            kpos = None
            if ring:
                slots = jnp.arange(t_cache)
                kpos = qpos[..., None] - (
                    (qpos[..., None] - slots[None, None])
                    % t_cache)                       # (B, 1, T) absolute
            out = _decode_attend(q, ck, cv, qpos, causal=causal,
                                 window=window, kpos=kpos)
        out = _merge_heads(out)
        out = dense(p, "o_proj", out, adapters=adapters, peft=peft)
        return out, {"k": ck, "v": cv}

    out = attention_core(q, k, v, causal=causal, window=window,
                         q_offset=q_offset, q_chunk=q_chunk)
    out = shard_heads(out, "out")
    out = _merge_heads(out)
    out = dense(p, "o_proj", out, adapters=adapters, peft=peft)
    return out, {"k": k, "v": v}


def _decode_attend(q, ck, cv, qpos, *, causal=True, window=None, kpos=None):
    """Single-token attention against a full preallocated cache.

    q: (B, H, 1, D); ck/cv: (B, Hkv, T, D); qpos: (B, 1) absolute position
    of the query. ``kpos`` optionally gives per-slot absolute positions
    (ring buffers); default is slot index == position.
    """
    b, h, _, d = q.shape
    hkv, t = ck.shape[1], ck.shape[2]
    rep = h // hkv
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, rep, 1, d)
    logits = jnp.einsum("bgrqd,bgtd->bgrqt", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * scale
    if kpos is None:
        kpos = jnp.broadcast_to(jnp.arange(t)[None, None], (b, 1, t))
    mask = kpos >= 0
    if causal:
        mask &= kpos <= qpos[:, :, None]
    if window is not None:
        mask &= kpos > qpos[:, :, None] - window
    logits = jnp.where(mask[:, None, None], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqt,bgtd->bgrqd", p, cv.astype(jnp.float32))
    return out.reshape(b, h, 1, d).astype(q.dtype)
