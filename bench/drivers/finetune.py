"""Finetuning driver: the system's ``Trainer`` (``runtime/trainer.py``,
the loop behind ``launch/train.py``) taking ETHER steps on a frozen base.

Set-up builds one ``Trainer`` for the configuration with AdamW on the
adapters as the mix file states it, puts the benchmark's weights and
initial adapters (made from the seed) into its state, and drives it
through the first ``checked_steps`` steps with ``Trainer.fit`` on the
benchmark's own token stream (random rows from the seed, all
different): these compile the step and are the steps the reference
follows.  The window then hands the same object one ``fit`` step at a
time until ``--seconds`` have passed; it closes at a step boundary.

End-to-end: ``finetune_step_ms`` is the time from the window's start to
the end of its last step over the number of steps (no partial step);
``setup_s`` is process start to window start.

``correct`` (after the window, the trainer freed, the weights made again
from the seed): the plain float32 reference (``bench/model.py``) takes
the same checked steps from the same adapters and batches.  Compared:
each step's loss (relative gap), the norm of the first step's gradient
as the optimizer got it (from Adam's first moment after one step:
mu / (1 - b1)) and the norm of the adapters' change over the checked
steps, both per leaf (one adapted projection of one layer) and by the
worst leaf: the gap between the program's norm and the reference's over
the larger of the reference's leaf norm and its median leaf norm.
Leaves whose reference gradient is under a thousandth of the median
leaf's are left out.  With ``Run.control`` the control (the reference
one precision below the configuration's) is put in the program's place
and compared instead; the program's numbers, and those of a reference
step over half the batch, are readings beside it.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
from typing import Optional

import numpy as np

from bench import harness, model, system


@dataclasses.dataclass
class StepRec:
    n: int
    t_start: float
    t_end: float
    loss: float
    traced: bool


@dataclasses.dataclass
class Layer:
    """What the per-layer readers read (``bench/metrics/*.py``)."""
    cfg: dict
    mix: dict
    peak: Optional[dict]
    steps: list


class Stream:
    """Random token rows from the seed; batch ``k`` is a function of
    (seed, k) alone."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self.seed = int(seed) % (1 << 64)

    def batch_at(self, step: int) -> dict:
        toks = np.random.default_rng((self.seed, step)).integers(
            0, self.vocab, (self.batch, self.seq + 1), dtype=np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _find(tree, key: str):
    """The first sub-tree stored under ``key`` in a nested state."""
    if isinstance(tree, dict):
        if key in tree:
            return tree[key]
        items = tree.values()
    elif isinstance(tree, (list, tuple)):
        items = tree
    else:
        return None
    for v in items:
        hit = _find(v, key)
        if hit is not None:
            return hit
    return None


def run(r: harness.Run) -> harness.Outcome:
    import time

    import jax
    import jax.numpy as jnp
    from repro.core import execute
    from repro.optim import adamw, constant
    from repro.runtime.trainer import Trainer
    cfg, mix = r.cfg, r.mix
    execute.reset_counters()
    o = mix["optimizer"]
    mcfg, peft = system.model_config(cfg), system.peft_config(cfg)
    opt = adamw(constant(o["lr"]), b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=0.0, clip_norm=o["clip"])
    trainer = Trainer(mcfg, peft, opt, restore="none")
    trainer.state = None                    # the program's own init
    gc.collect()
    weights = model.make_weights(cfg, r.seed)
    a0 = model.adapter_fn(cfg, r.seed)(0)
    system.check_layout(cfg, mcfg, peft, weights, a0)
    a0_host = jax.device_get(a0)
    trainer.state = {"params": weights, "adapters": a0,
                     "opt_state": opt.init(a0),
                     "step": jnp.zeros((), jnp.int32)}
    del weights
    stream = Stream(cfg["vocab_size"], mix["batch"], mix["seq"], r.seed)

    def now():
        return time.perf_counter() - r.t_proc

    k_steps = mix["checked_steps"]
    losses, g1 = [], None
    for k in range(1, k_steps + 1):
        losses.append(trainer.fit(stream, steps=k)["loss"])
        if k == 1:
            g1 = jax.tree_util.tree_map(
                lambda m: np.asarray(m) / (1 - o["b1"]),
                jax.device_get(_find(trainer.state["opt_state"], "mu")))
    theta = jax.device_get(trainer.state["adapters"])

    # -- the window ---------------------------------------------------
    steps: list = []
    log_dir = span = None
    if r.trace:
        log_dir = harness.trace_dir()
        jax.profiler.start_trace(log_dir)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
    compiles0 = harness.Compiles.count()
    t0 = now()
    t1 = t0 + r.seconds
    t_trace = t0 + mix["trace_seconds"]
    while now() < t1:
        n = len(steps)
        ts = now()
        with jax.profiler.TraceAnnotation("bench.step", n=n):
            m = trainer.fit(stream, steps=trainer.step + 1)
        te = now()
        steps.append(StepRec(n, ts, te, m["loss"], span is not None))
        if span is not None and te >= t_trace:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            span = None
    if span is not None:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles = harness.Compiles.count() - compiles0
    e2e = {"finetune_step_ms": (steps[-1].t_end - t0) / len(steps) * 1e3,
           "setup_s": t0}
    mem = harness.memory_peak(r.cell["chips"])

    # -- after the window: free the trainer, then the reference -------
    del trainer
    gc.collect()
    checks, readings = _check(cfg, mix, r.seed, stream, a0_host, losses,
                              g1, theta, r.control)
    checks["window_compiles"] = (float(compiles), 0.0)
    readings["adapter_ops"] = execute.counters()
    trace = None
    if log_dir is not None:
        from bench import trace as tr
        trace = tr.load(tr.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    failed = sum(1 for s in steps if not np.isfinite(s.loss))
    return harness.Outcome(
        e2e=e2e, layer=Layer(cfg, mix, r.peak, steps), checks=checks,
        attempted=len(steps), failed=failed, memory_peak=mem, trace=trace,
        readings=readings)


def _leaf_norms(tree) -> dict:
    """Norm of each layer's slice of each adapter leaf (L, n, db)."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf, np.float64).reshape(leaf.shape[0], -1)
        name = jax.tree_util.keystr(path)
        for i, v in enumerate(np.sqrt((a * a).sum(-1))):
            out[(name, i)] = v
    return out


def worst_leaf(got: dict, want: dict, keep) -> float:
    """max over kept leaves of |got - want| / max(want, median want)."""
    med = float(np.median([want[k] for k in keep]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keep)


def reference_readings(cfg, mix, seed, stream, a0, quant=None, rows=None):
    """The reference's (or a stand-in's) losses, first clipped gradient
    and adapters after the checked steps."""
    import jax
    import jax.numpy as jnp
    weights = model.make_weights(cfg, seed)
    step = model.train_steps(cfg, mix["optimizer"], quant, rows)
    a = jax.tree_util.tree_map(jnp.asarray, a0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, a)
    nu = jax.tree_util.tree_map(jnp.zeros_like, a)
    count = jnp.zeros((), jnp.int32)
    losses, g1 = [], None
    for k in range(mix["checked_steps"]):
        b = stream.batch_at(k)
        loss, g, a, mu, nu, count = step(weights, a, mu, nu, count,
                                         b["tokens"], b["labels"])
        losses.append(float(loss))
        if k == 0:
            g1 = jax.device_get(g)
    out = losses, g1, jax.device_get(a)
    del weights
    return out


def compare(a0, prog, ref) -> dict:
    """The three compared numbers of a run ``prog`` against ``ref``, each
    ``(losses, first gradient, adapters after the checked steps)``."""
    import jax
    lp, gp, tp = prog
    lr, gr, tr_ = ref
    ng_r, ng_p = _leaf_norms(gr), _leaf_norms(gp)
    med = float(np.median(list(ng_r.values())))
    keep = [k for k, v in ng_r.items() if v >= 1e-3 * med]
    dp = _leaf_norms(jax.tree_util.tree_map(np.subtract, tp, a0))
    dr = _leaf_norms(jax.tree_util.tree_map(np.subtract, tr_, a0))
    return {"loss_gap": max(abs(p - q) / abs(q) for p, q in zip(lp, lr)),
            "grad_norm_gap": worst_leaf(ng_p, ng_r, keep),
            "change_norm_gap": worst_leaf(dp, dr, keep),
            "leaves_left_out": len(ng_r) - len(keep)}


def _check(cfg, mix, seed, stream, a0, losses, g1, theta, control: bool):
    ref = reference_readings(cfg, mix, seed, stream, a0)
    got = compare(a0, (losses, g1, theta), ref)
    lim = cfg["check"]["finetune"]
    names = ("loss_gap", "grad_norm_gap", "change_norm_gap")
    readings = {"leaves_left_out": got["leaves_left_out"],
                "losses": losses, "reference_losses": ref[0]}
    if control:
        ctl = compare(a0, reference_readings(cfg, mix, seed, stream, a0,
                                             quant=mix["control"]), ref)
        half = compare(a0, reference_readings(cfg, mix, seed, stream, a0,
                                              rows=mix["batch"] // 2), ref)
        for k in names:
            readings[f"program_{k}"] = got[k]
            readings[f"half_batch_{k}"] = half[k]
        got = ctl
    return {k: (got[k], lim[k]) for k in names}, readings
