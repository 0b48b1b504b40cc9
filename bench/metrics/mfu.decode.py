"""Whole decode step's share of the chip's roofline (%): for each traced
bank or merged decode step, the least time the chip needs for the
step's work (``counts.decode_step``: every weight once, the live KV, the
gathered bank rows) over the device time spent inside that step's
``bench.step`` span; summed over the traced steps before dividing.
Layer: whole decode step.  Moves ``tok_s``."""

from bench import counts
from bench import trace as tr


def read(out):
    t, lay = out.trace, out.layer
    if t is None or lay.peak is None:
        return None
    spans = tr.step_spans(t)
    need = spent = 0.0
    for s in lay.steps:
        span = spans.get(s.n)
        if span is None or not s.traced or not s.ctx_lens:
            continue
        work = counts.decode_step(lay.cfg, s.ctx_lens, s.tenants,
                                  bank=s.tier == "bank")
        need += counts.seconds(work, lay.peak)[0]
        spent += tr.busy(t, span.start, span.end)
    return 100.0 * need / spent if spent > 0 else None
