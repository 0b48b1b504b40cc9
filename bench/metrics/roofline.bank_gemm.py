"""The Pallas bank reflect-GEMM kernel's share of its roofline (%) at
decode: for each traced bank-tier decode step, the least time the chip
needs for the step's batched reflect-GEMMs (``counts.decode_bank_gemms``:
W once per call, the active rows, the gathered bank rows) over the
device time of the kernel's operations inside the step's ``bench.step``
span.  Prefill's calls (inside ``bench.admit``) are left out.  Nothing
to read where the kernel does not run (its op is then an XLA fusion).
Layer: adapter kernels.  Moves ``tok_s``."""

from bench import counts
from bench import trace as tr

KERNEL = ("householder_gemm_batched", "_hh_gemm_batched_kernel")


def read(out):
    t, lay = out.trace, out.layer
    if t is None or lay.peak is None:
        return None
    spans = tr.step_spans(t)
    need = spent = 0.0
    for s in lay.steps:
        span = spans.get(s.n)
        if span is None or not s.traced or s.tier != "bank" or not s.ctx_lens:
            continue
        k = [o for o in t.ops_in(span.start, span.end) if tr.matches(o, KERNEL)]
        if not k:
            continue
        work = counts.decode_bank_gemms(lay.cfg, len(s.ctx_lens), s.tenants)
        need += counts.seconds(work, lay.peak)[0]
        spent += sum(o.dur for o in k)
    return 100.0 * need / spent if spent > 0 else None
