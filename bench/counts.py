"""Work that a step needs, from the configuration's shapes alone.

Every count is ``(flops, bytes)``: the operations and the HBM bytes that
the computation needs, whatever implements it.  Work that an
implementation adds (re-reading a weight per sequence, attending over
positions past a sequence's end, recomputing activations) is not
counted, so a share of the roofline above 100% means the count or the
timed window is wrong, never that the program beat the chip.

This module holds what every architecture shares: the sums over the
projection list of ``harness.arch(cfg).projections`` and the fused
reflect-GEMM's own work.  A step's counts (``weight_bytes``,
``decode_step``, ``prefill``, ``train_step``) are the architecture
module's, which builds them from these.  bf16 weights, activations and
KV cache (2 bytes), float32 adapter vectors (4 bytes).
"""

from __future__ import annotations

from bench import harness, model

BF16 = 2
F32 = 4


def matmul_params(units) -> int:
    """Parameters of every projection of every layer."""
    return sum(u.count * sum(di * do for di, do in u.shapes.values())
               for u in units)


def reflected_width(cfg: dict, units) -> int:
    """Sum over every layer of the input widths of its targeted
    projections: one bank row of the whole model, and the size of a
    token's reflections."""
    return sum(u.count * sum(u.shapes[p][0]
                             for p in model.targets(cfg, u.shapes))
               for u in units)


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
    flops = nbytes = 0
    for u in harness.arch(cfg).projections(cfg):
        out = [reflect_gemm(active, *u.shapes[p], tenants)
               for p in model.targets(cfg, u.shapes)]
        flops += u.count * sum(f for f, _ in out)
        nbytes += u.count * sum(b for _, b in out)
    return flops, nbytes


def reflect_gemm_train(tokens: int, d: int, f: int):
    """Forward and backward of one fused reflect-GEMM with a frozen W.

    Forward as :func:`reflect_gemm`.  Backward: dx = H (dy W^T) and the
    hyperplane's gradient; no weight gradient (W is frozen).  Counted:
    2 tokens d f + 8 tokens d operations; W, dy, x and dx once."""
    fwd = reflect_gemm(tokens, d, f)
    bwd = (2 * tokens * d * f + 8 * tokens * d,
           BF16 * (d * f + tokens * f + 2 * tokens * d) + F32 * 2 * d)
    return fwd[0] + bwd[0], fwd[1] + bwd[1]


def seconds(work, peak: dict) -> tuple[float, str]:
    """Least time the chip needs for ``(flops, bytes)``, and which bound."""
    flops, nbytes = work
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "flops") if tc >= tm else (tm, "bytes")
