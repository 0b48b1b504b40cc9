"""Work that a step needs, from the configuration's shapes alone.

Every function returns ``(flops, bytes)``: the operations and the HBM
bytes that the computation needs, whatever implements it.  Work that an
implementation adds (re-reading a weight per sequence, attending over
positions past a sequence's end, recomputing activations) is not
counted, so a share of the roofline above 100% means the count or the
timed window is wrong, never that the program beat the chip.

Widths come from ``model.dims``; bf16 weights, activations and KV cache
(2 bytes), float32 adapter vectors (4 bytes).
"""

from __future__ import annotations

from bench import model

BF16 = 2
F32 = 4


def _layer_matmul_params(cfg: dict) -> int:
    return sum(di * do for di, do in model.kernel_shapes(cfg).values())


def _adapter_width(cfg: dict) -> int:
    """Sum of the input widths of the targeted projections of one layer:
    one bank row of one layer, and the size of its reflections."""
    shapes = model.kernel_shapes(cfg)
    return sum(shapes[t][0] for t in model.targets(cfg))


def weight_bytes(cfg: dict) -> int:
    """Every weight the forward reads once: projections, norms and the
    output head's matrix (the embedding table when tied, the head when
    not).  Not counted: the embedding rows an untied model gathers (a
    few kilobytes), biases (the program's block has none)."""
    m = model.dims(cfg)
    per_layer = _layer_matmul_params(cfg) + 2 * m["d"]
    return BF16 * (m["L"] * per_layer + m["V"] * m["d"] + m["d"])


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
    m = model.dims(cfg)
    b = len(ctx_lens)
    live = sum(ctx_lens)
    kv_row = 2 * m["Hkv"] * m["hd"] * BF16            # k and v, one position
    flops = b * (2 * m["L"] * _layer_matmul_params(cfg) + 2 * m["d"] * m["V"])
    flops += 4 * m["L"] * m["H"] * m["hd"] * live
    nbytes = weight_bytes(cfg) + m["L"] * kv_row * (live + b)
    if bank:
        aw = _adapter_width(cfg)
        flops += b * m["L"] * 4 * aw
        nbytes += tenants * m["L"] * aw * F32
    return flops, nbytes


def prefill(cfg: dict, prompt_len: int, bank: bool = True):
    """One request's prefill of ``prompt_len`` real tokens.

    Counted: every weight once; projections, causal attention (half the
    square), reflections, the keys and values written, one bank row, and
    the output head at the last position only.  Not counted: pad tokens
    up to the engine's bucket."""
    m = model.dims(cfg)
    p = prompt_len
    flops = 2 * m["L"] * _layer_matmul_params(cfg) * p + 2 * m["d"] * m["V"]
    flops += 2 * m["L"] * m["H"] * m["hd"] * p * p
    nbytes = weight_bytes(cfg) + m["L"] * 2 * m["Hkv"] * m["hd"] * BF16 * p
    if bank:
        aw = _adapter_width(cfg)
        flops += 4 * m["L"] * aw * p
        nbytes += m["L"] * aw * F32
    return flops, nbytes


def reflect_gemm(rows: int, d: int, f: int, tenants: int = 1):
    """One fused reflect-GEMM over ``rows`` tokens: y = (H x) W.

    Counted: the product (2 rows d f), the reflection (4 rows d: one
    projection on the hyperplane, one update), W once, x and y once, and
    ``tenants`` gathered bank rows of d float32 values.  Not counted: W
    read again per sequence or per row tile."""
    flops = 2 * rows * d * f + 4 * rows * d
    nbytes = BF16 * (d * f + rows * d + rows * f) + F32 * tenants * d
    return flops, nbytes


def decode_bank_gemms(cfg: dict, active: int, tenants: int):
    """All bank reflect-GEMM calls of one decode step: one per targeted
    projection per layer, ``active`` rows each."""
    m = model.dims(cfg)
    shapes = model.kernel_shapes(cfg)
    out = [reflect_gemm(active, *shapes[t], tenants)
           for t in model.targets(cfg)]
    return (m["L"] * sum(f for f, _ in out), m["L"] * sum(b for _, b in out))


def reflect_gemm_train(tokens: int, d: int, f: int):
    """Forward and backward of one fused reflect-GEMM with a frozen W.

    Forward as :func:`reflect_gemm`.  Backward: dx = H (dy W^T) and the
    hyperplane's gradient; no weight gradient (W is frozen).  Counted:
    2 tokens d f + 8 tokens d operations; W, dy, x and dx once."""
    fwd = reflect_gemm(tokens, d, f)
    bwd = (2 * tokens * d * f + 8 * tokens * d,
           BF16 * (d * f + tokens * f + 2 * tokens * d) + F32 * 2 * d)
    return fwd[0] + bwd[0], fwd[1] + bwd[1]


def train_step(cfg: dict, batch: int, seq: int):
    """One finetuning step of ``batch`` x ``seq`` tokens, frozen base.

    Counted: forward projections and output head (2 N per token), their
    input gradients in the backward (2 N per token, no weight gradients),
    causal attention forward (half the square) and backward (twice the
    forward), and the reflections forward and backward.  Not counted:
    the forward recomputed under rematerialisation, the optimizer's
    elementwise update.  Bytes: every weight read twice (forward,
    backward); activations are not counted."""
    m = model.dims(cfg)
    t = batch * seq
    n = m["L"] * _layer_matmul_params(cfg) + m["d"] * m["V"]
    attn = 2 * m["L"] * m["H"] * m["hd"] * seq * seq * batch
    flops = 4 * n * t + 3 * attn + 12 * m["L"] * _adapter_width(cfg) * t
    return flops, 2 * weight_bytes(cfg)


def seconds(work, peak: dict) -> tuple[float, str]:
    """Least time the chip needs for ``(flops, bytes)``, and which bound."""
    flops, nbytes = work
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "flops") if tc >= tm else (tm, "bytes")
