"""Admission wait, 95th percentile (ms): engine admission stamp minus the
moment the client sent the request, over the requests sent inside the
window.  Layer: admission.  Moves ``tok_s`` (in a closed loop a slot
waits empty while its next request waits)."""

import numpy as np


def read(out):
    w = [(q.admit_s - q.arrival_s) * 1e3 for q in out.layer.requests
         if q.admit_s is not None]
    return float(np.percentile(w, 95)) if w else None
