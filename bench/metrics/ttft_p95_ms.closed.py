"""Time to first token, 95th percentile (ms), in a closed loop: first
token minus the moment the client sent the request, over the requests
sent inside the window.  At capacity this tail swings with the smallest
change, so it is recorded here and judged nowhere.  Layer: client.
Moves ``tok_s``."""

import numpy as np


def read(out):
    w = out.layer.ttft_ms
    return float(np.percentile(w, 95)) if w else None
