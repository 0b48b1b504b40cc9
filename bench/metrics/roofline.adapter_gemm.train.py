"""The fused ETHER reflect-GEMM kernels' share of their roofline (%) in
finetuning: for each traced step, the least time the chip needs for the
forward and backward of every adapted projection
(``counts.reflect_gemm_train``: W, x, dy, dx once each, no weight
gradient) over the device time of the forward kernel
(``householder_gemm_pallas``) and the backward's dx/du kernel
(``reflect_gemm_dx_pallas``) inside the step's ``bench.step`` span.  The
forward recomputed under rematerialisation is kernel time but not needed
work.  Nothing to read where the kernels do not run.
Layer: adapter kernels.  Moves ``finetune_step_ms``."""

from bench import counts, model
from bench import trace as tr

KERNELS = ("householder_gemm_pallas", "reflect_gemm_dx_pallas")


def read(out):
    t, lay = out.trace, out.layer
    if t is None or lay.peak is None:
        return None
    tokens = lay.mix["batch"] * lay.mix["seq"]
    shapes = model.kernel_shapes(lay.cfg)
    L = model.dims(lay.cfg)["L"]
    per_step = sum(counts.seconds(counts.reflect_gemm_train(
        tokens, *shapes[n]), lay.peak)[0] for n in model.targets(lay.cfg)) * L
    spans = tr.step_spans(t)
    need = spent = 0.0
    for s in lay.steps:
        span = spans.get(s.n)
        if span is None or not s.traced:
            continue
        k = [o for o in t.ops_in(span.start, span.end)
             if tr.matches(o, KERNELS)]
        if k:
            need += per_step
            spent += sum(o.dur for o in k)
    return 100.0 * need / spent if spent > 0 else None
