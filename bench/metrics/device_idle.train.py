"""Device idle share (%) of the traced part of the window: one minus the
union of device-operation intervals over the traced window.
Layer: device.  Moves ``finetune_step_ms``."""

from bench import trace as tr


def read(out):
    if out.trace is None or not out.trace.ops:
        return None
    busy, window = tr.busy_per_device(out.trace)
    return 100.0 * (1.0 - busy / window) if window > 0 else None
