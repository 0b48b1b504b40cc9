"""The dense decoder block: pre-norm RMSNorm, rotary grouped-query
attention, and a GELU (tanh) two-matrix or SwiGLU MLP, in every layer.

The architecture of a configuration file with no ``arch`` key (see
``bench/harness.py`` for the interface).  The stack is one pattern
position, ``units/pos0``, of ``num_hidden_layers`` layers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import counts, model
from bench.counts import BF16, F32

# what the program's dense block hard-codes (models/layers.py, backbone.py)
PROGRAM_NORM_EPS = 1e-6
MIXER = ("q_proj", "k_proj", "v_proj", "o_proj")


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


def _shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    """(d_in, d_out) of every per-layer projection, by name."""
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


def projections(cfg: dict) -> list:
    return [model.Unit("units/pos0", dims(cfg)["L"],
                       {f"{_group(n)}/{n}": s
                        for n, s in _shapes(cfg).items()})]


def make_weights(cfg: dict, seed: int):
    """Random bf16 weights in the system's tree, in one jitted call on
    the device: lecun-normal projections, N(0, 0.02) embeddings, unit
    norm scales."""
    m = dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    shapes = _shapes(cfg)

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

    return jax.jit(build)(model.key(seed, 1))


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

    def layer(x, xs):
        lw, la = xs
        h = model._rmsnorm(x, lw["norm1"]["scale"].astype(f32), m["eps"])
        x = x + model.attention(lw, la, h, pos, mask, m["H"], m["Hkv"],
                                m["theta"], quant)
        h = model._rmsnorm(x, lw["norm2"]["scale"].astype(f32), m["eps"])
        return x + model.mlp(lw, la, h, m["glu"], quant), None

    if remat:
        layer = jax.checkpoint(layer)
    x = weights["embed"]["table"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, (weights["units"]["pos0"],
                                   adapter["units"]["pos0"]))
    return model._rmsnorm(x, weights["final_norm"]["scale"].astype(f32),
                          m["eps"])


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of this block; refuses what the
    program's dense block cannot run."""
    from repro.models import ModelConfig
    m = dims(cfg)
    if m["eps"] != PROGRAM_NORM_EPS:
        raise ValueError(f"{cfg['name']}: norm eps {m['eps']} != the "
                         f"program's {PROGRAM_NORM_EPS}")
    if cfg.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError(f"{cfg['name']}: the program rotates whole heads")
    prog = cfg["program"]
    return ModelConfig(
        name=cfg["name"], n_layers=m["L"], d_model=m["d"], n_heads=m["H"],
        n_kv=m["Hkv"], d_ff=m["ff"], vocab=m["V"],
        mlp_type="swiglu" if m["glu"] else "gelu",
        act="silu" if m["glu"] else "gelu", rope_theta=m["theta"],
        tie_embeddings=m["tied"], param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["torch_dtype"], remat=prog["remat"],
        q_chunk=prog["q_chunk"], loss_chunk=prog["loss_chunk"])


# ---------------------------------------------------------------------------
# Work counts (see bench/counts.py for what is and is not counted)
# ---------------------------------------------------------------------------

def weight_bytes(cfg: dict) -> int:
    """Every weight the forward reads once: projections, norms and the
    output head's matrix (the embedding table when tied, the head when
    not).  Not counted: the embedding rows an untied model gathers (a
    few kilobytes), biases (the program's block has none)."""
    m = dims(cfg)
    params = counts.matmul_params(projections(cfg)) + 2 * m["d"] * m["L"]
    return BF16 * (params + m["V"] * m["d"] + m["d"])


def decode_step(cfg: dict, ctx_lens, tenants: int, bank: bool = True):
    """One batched decode step.

    ``ctx_lens``: for each active sequence, the positions its new token
    attends to (its prompt and generated tokens so far, the new one
    included).  ``tenants``: distinct tenants among them.

    Counted: every weight once; the live keys and values of each
    sequence, read, and the new token's written; the bank rows of the
    ``tenants`` distinct tenants (``bank``); per token the projections,
    the attention over its live positions, the reflections (``bank``)
    and the output head.  Not counted: inactive slots, positions past a
    sequence's end, a weight read again per sequence, activations
    (kilobytes at decode)."""
    m = dims(cfg)
    units = projections(cfg)
    b = len(ctx_lens)
    live = sum(ctx_lens)
    kv_row = 2 * m["Hkv"] * m["hd"] * BF16            # k and v, one position
    flops = b * (2 * counts.matmul_params(units) + 2 * m["d"] * m["V"])
    flops += 4 * m["L"] * m["H"] * m["hd"] * live
    nbytes = weight_bytes(cfg) + m["L"] * kv_row * (live + b)
    if bank:
        rw = counts.reflected_width(cfg, units)
        flops += b * 4 * rw
        nbytes += tenants * rw * F32
    return flops, nbytes


def prefill(cfg: dict, prompt_len: int, bank: bool = True):
    """One request's prefill of ``prompt_len`` real tokens.

    Counted: every weight once; projections, causal attention (half the
    square), reflections, the keys and values written, one bank row, and
    the output head at the last position only.  Not counted: pad tokens
    up to the engine's bucket."""
    m = dims(cfg)
    units = projections(cfg)
    p = prompt_len
    flops = 2 * counts.matmul_params(units) * p + 2 * m["d"] * m["V"]
    flops += 2 * m["L"] * m["H"] * m["hd"] * p * p
    nbytes = weight_bytes(cfg) + m["L"] * 2 * m["Hkv"] * m["hd"] * BF16 * p
    if bank:
        rw = counts.reflected_width(cfg, units)
        flops += 4 * rw * p
        nbytes += rw * F32
    return flops, nbytes


def train_step(cfg: dict, batch: int, seq: int):
    """One finetuning step of ``batch`` x ``seq`` tokens, frozen base.

    Counted: forward projections and output head (2 N per token), their
    input gradients in the backward (2 N per token, no weight gradients),
    causal attention forward (half the square) and backward (twice the
    forward), and the reflections forward and backward.  Not counted:
    the forward recomputed under rematerialisation, the optimizer's
    elementwise update.  Bytes: every weight read twice (forward,
    backward); activations are not counted."""
    m = dims(cfg)
    units = projections(cfg)
    t = batch * seq
    n = counts.matmul_params(units) + m["d"] * m["V"]
    attn = 2 * m["L"] * m["H"] * m["hd"] * seq * seq * batch
    flops = 4 * n * t + 3 * attn + 12 * counts.reflected_width(cfg, units) * t
    return flops, 2 * weight_bytes(cfg)
