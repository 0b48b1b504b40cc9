"""Decode step, median host time (ms) of one ``ServeEngine.step`` started
inside the window (the step ends on a device fetch, so this is the
device's step plus the host's share).  Layer: engine decode step.
Moves ``tok_s``."""

import numpy as np


def read(out):
    w = [(s.t_end - s.t_start) * 1e3 for s in out.layer.steps]
    return float(np.median(w)) if w else None
