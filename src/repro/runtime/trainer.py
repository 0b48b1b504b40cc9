"""The production train loop: sharded step, async checkpoints,
auto-resume, preemption handling, straggler watchdog, failure injection.

This is the engine behind launch/train.py and the fault-tolerance tests:
    trainer = Trainer(cfg, peft, opt, mesh=mesh, ckpt_dir=...)
    trainer.fit(stream, steps=500)

Fault-tolerance contract:
* every ``ckpt_every`` steps the full state (adapters + opt + data cursor
  + step) is snapshotted asynchronously and atomically;
* SIGTERM/SIGINT (preemption) → synchronous checkpoint, clean exit;
* on construction with ``restore='auto'`` the latest checkpoint is
  loaded and the data stream resumes at the exact step;
* ``fail_at_step`` raises mid-run (tests use it to prove restart works);
* the StepTimer flags straggler steps (see runtime/straggler.py).
"""

from __future__ import annotations

import json
import os
import signal
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager, latest_step
from repro.core.transforms import PEFTConfig
from repro.data.pipeline import DataState
from repro.launch.steps import (abstract_state, batch_shardings, init_state,
                                make_train_step, state_shardings)
from repro.optim import GradientTransformation
from repro.parallel.context import MeshContext, mesh_context

Params = dict[str, Any]


class Trainer:
    def __init__(self, cfg, peft: Optional[PEFTConfig],
                 opt: GradientTransformation, *, mesh=None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 restore: str = "auto", full_finetune: bool = False,
                 seed: int = 0, log_path: Optional[str] = None,
                 fail_at_step: Optional[int] = None,
                 metrics_hook: Optional[Callable[[int, dict], None]] = None):
        self.cfg = cfg
        self.peft = peft
        self.opt = opt
        self.mesh = mesh
        self.full_finetune = full_finetune
        self.ckpt_every = ckpt_every
        self.fail_at_step = fail_at_step
        self.metrics_hook = metrics_hook
        self.log_path = log_path
        self.data_state = DataState()
        self._stop = False
        self._log_f = open(log_path, "a") if log_path else None

        self.ckpt = (CheckpointManager(ckpt_dir) if ckpt_dir else None)
        self.timer = _make_timer()

        step_fn = make_train_step(cfg, peft, opt,
                                  full_finetune=full_finetune)
        if mesh is not None:
            state_sds = abstract_state(cfg, peft, opt,
                                       full_finetune=full_finetune)
            self._st_sh = state_shardings(state_sds, mesh)
            self.step_fn = None       # jit lazily once batch shape is known
            self._raw_step = step_fn
        else:
            self._st_sh = None
            self.step_fn = jax.jit(step_fn, donate_argnums=(0,))
            self._raw_step = step_fn

        # ---- init or restore ----
        restored = False
        if self.ckpt and restore == "auto" and \
                latest_step(self.ckpt.root) is not None:
            state_sds = abstract_state(cfg, peft, opt,
                                       full_finetune=full_finetune)
            tree, extra = self.ckpt.restore(template=state_sds,
                                            shardings=self._st_sh)
            self.state = tree
            self.data_state = DataState.from_dict(extra["data"])
            restored = True
        if not restored:
            self.state = self._init_state(seed)

        signal.signal(signal.SIGTERM, self._preempt)
        try:
            signal.signal(signal.SIGINT, self._preempt)
        except ValueError:            # non-main thread (tests)
            pass

    def _init_state(self, seed):
        rng = jax.random.PRNGKey(seed)
        if self.mesh is None:
            return init_state(rng, self.cfg, self.peft, self.opt,
                              full_finetune=self.full_finetune)
        with mesh_context(MeshContext(self.mesh)):
            init = jax.jit(
                lambda r: init_state(r, self.cfg, self.peft, self.opt,
                                     full_finetune=self.full_finetune),
                out_shardings=self._st_sh)
            return init(rng)

    # ------------------------------------------------------------------ api
    @property
    def step(self) -> int:
        return int(self.state["step"])

    def _preempt(self, signum, frame):
        self._stop = True

    def _jit_for_batch(self, batch):
        if self.step_fn is not None:
            return
        b_sh = batch_shardings(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
            self.mesh)
        self.step_fn = jax.jit(self._raw_step,
                               in_shardings=(self._st_sh, b_sh),
                               out_shardings=(self._st_sh, None),
                               donate_argnums=(0,))

    def save(self, *, block: bool = False):
        if not self.ckpt:
            return
        with jax.profiler.TraceAnnotation("trainer.checkpoint"):
            self.ckpt.save(self.step, self.state,
                           extra={"data": self.data_state.to_dict()},
                           block=block)

    def fit(self, stream, *, steps: int) -> dict:
        """Run ``steps`` optimizer steps from the stream's cursor.

        Each iteration is traced as the span ``trainer.step`` (stat
        ``step``) with children ``trainer.batch`` (reading the batch; its
        transfer rides in the step's dispatch), ``trainer.fetch`` (the
        metrics' transfer, which waits for the step) and
        ``trainer.checkpoint``."""
        ctx = (mesh_context(MeshContext(self.mesh)) if self.mesh is not None
               else _null_ctx())
        last_metrics: dict = {}
        with ctx:
            while (step := self.step) < steps and not self._stop:
                with jax.profiler.TraceAnnotation("trainer.step", step=step):
                    with jax.profiler.TraceAnnotation("trainer.batch"):
                        batch_np = stream.batch_at(self.data_state.step)
                        self._jit_for_batch(batch_np)
                    batch = batch_np
                    self.timer.start()
                    self.state, metrics = self.step_fn(self.state, batch)
                    with jax.profiler.TraceAnnotation("trainer.fetch"):
                        metrics = {k: float(v) for k, v in metrics.items()}
                    dt = self.timer.stop(self.step)
                    self.data_state.step += 1
                    last_metrics = dict(metrics, step=self.step,
                                        step_time=dt)
                    self._log(last_metrics)
                    if self.metrics_hook:
                        self.metrics_hook(self.step, last_metrics)
                    if self.fail_at_step is not None \
                            and self.step == self.fail_at_step:
                        raise RuntimeError(
                            f"injected failure at step {self.step}")
                    if self.ckpt and self.step % self.ckpt_every == 0:
                        self.save()
        if self.ckpt:
            self.save(block=True)
            self.ckpt.wait()
        return last_metrics

    def _log(self, metrics: dict):
        if self._log_f:
            self._log_f.write(json.dumps(metrics) + "\n")
            self._log_f.flush()


def _make_timer():
    from repro.runtime.straggler import StepTimer
    return StepTimer(on_straggler=lambda step, dt, mean: print(
        f"[straggler] step {step}: {dt:.3f}s vs mean {mean:.3f}s",
        flush=True))


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
