"""Prefill time, median (ms): first token minus admission stamp, over the
requests sent inside the window.  Layer: engine admit (batch-1 prefill
into a slot, first token sampled).  Moves ``tok_s`` (a prefill stalls
every slot's decoding)."""

import numpy as np


def read(out):
    w = [(q.first_token_s - q.admit_s) * 1e3 for q in out.layer.requests
         if q.first_token_s is not None and q.admit_s is not None]
    return float(np.median(w)) if w else None
