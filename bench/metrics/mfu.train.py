"""Model FLOP/s utilization of finetuning (%): the operations one step
needs (``counts.train_step``: forward and input-gradient backward of a
frozen base, attention, reflections; recomputation not counted) times
steps per second of the run's window (host clock), over the chip's bf16
peak.  Layer: whole train step.  Moves ``finetune_step_ms``."""

from bench import counts


def read(out):
    lay = out.layer
    if lay.peak is None or not lay.steps:
        return None
    flops, _ = counts.train_step(lay.cfg, lay.mix["batch"], lay.mix["seq"])
    secs = (lay.steps[-1].t_end - lay.steps[0].t_start) / len(lay.steps)
    return 100.0 * flops / secs / lay.peak["bf16_flops_per_s"]
