"""Gap between output tokens, 95th percentile (ms), in a closed loop:
(finish - first token) / (tokens - 1) over the requests completed inside
the window, other requests' prefills included.  At capacity this tail
swings with the smallest change, so it is recorded here and judged
nowhere.  Layer: client.  Moves ``tok_s``."""

import numpy as np


def read(out):
    w = out.layer.tpot_ms
    return float(np.percentile(w, 95)) if w else None
