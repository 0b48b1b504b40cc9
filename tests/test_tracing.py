"""The program's own trace spans and device scopes (PERF.md §3).

Host spans (``jax.profiler.TraceAnnotation``) nest as the benchmark's
readers expect and carry their stats; they are recorded here under
``jax.profiler.trace`` on the CPU.  Device scopes (``jax.named_scope``)
are trace-time names: the lowered programs must hold the projection,
adapter, attention, sampling and recomputation scopes, under the
engine's own module names.
"""

import dataclasses
import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, peft_targets
from repro.core.transforms import PEFTConfig
from repro.data.pipeline import SyntheticLMStream
from repro.launch.steps import init_state, make_train_step
from repro.models import init_model
from repro.optim import adamw, constant
from repro.runtime.trainer import Trainer
from repro.serving import AdapterRegistry, Request, ServeEngine

ARCH = "phi-1.5"
PROGRAM = ("engine.", "registry.", "trainer.")


def _peft(backend="jnp"):
    return PEFTConfig(method="ether", n_blocks=4, targets=peft_targets(ARCH),
                      backend=backend)


@pytest.fixture(scope="module")
def engine():
    cfg = get_config(ARCH, "smoke")
    params = init_model(jax.random.PRNGKey(0), cfg)
    registry = AdapterRegistry(params, _peft(), 2, n_tenants=4)
    return ServeEngine(cfg, params, registry, _peft(), slots=2,
                       prompt_buckets=(16,), max_new_tokens=4)


def _spans(log_dir) -> dict:
    """The program's host spans of a trace: name -> [(start, end, stats)]."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


def _within(child, parent) -> bool:
    return parent[0] <= child[0] and child[1] <= parent[1]


def _request(rid, tenant):
    return Request(rid=rid, tenant_id=tenant,
                   prompt=np.arange(1, 10, dtype=np.int32),
                   max_new_tokens=4, arrival_s=0.0)


def test_engine_and_registry_spans_nest(engine, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        engine.admit(_request(7, 3))          # tenant 3 not in the bank
        engine.admit(_request(8, 3))          # now it is
        engine.step()
    s = _spans(tmp_path)
    first, second = s["engine.admit"]
    assert first[2] == {"rid": 7, "tenant": 3, "prompt_len": 9,
                        "bucket": 16}
    miss, hit = s["registry.acquire"]
    assert miss[2] == {"tenant": 3, "hit": 0}
    assert hit[2] == {"tenant": 3, "hit": 1}
    assert _within(miss, first) and _within(hit, second)
    (swap,) = s["registry.swap"]              # on the miss only
    assert _within(swap, miss) and swap[2]["slot"] in (0, 1)
    prefills = s["engine.prefill"]
    assert [p[2] for p in prefills] == [{"bucket": 16}] * 2
    assert _within(prefills[0], first) and _within(prefills[1], second)
    assert not _within(prefills[0], miss)
    (step,) = s["engine.step"]
    assert step[2] == {"ordinal": 0, "tier": 0, "active": 2, "tenants": 1}
    (fetch,) = s["engine.step.fetch"]
    assert _within(fetch, step) and fetch[2] == {}


def test_trainer_spans_nest(tmp_path):
    cfg = get_config("smollm-360m", "smoke")
    trainer = Trainer(cfg, PEFTConfig(method="ether", n_blocks=4,
                                      targets=peft_targets("smollm-360m")),
                      adamw(constant(1e-3)), ckpt_dir=str(tmp_path / "ck"),
                      ckpt_every=2)
    stream = SyntheticLMStream(vocab=cfg.vocab, batch=2, seq_len=16, seed=0)
    with jax.profiler.trace(str(tmp_path / "trace")):
        trainer.fit(stream, steps=2)
    s = _spans(tmp_path / "trace")
    steps = s["trainer.step"]
    assert [st[2] for st in steps] == [{"step": 0}, {"step": 1}]
    for name in ("trainer.batch", "trainer.fetch"):
        assert [sum(_within(c, p) for c in s[name]) for p in steps] == [1, 1]
    # the second step saves inside its span; fit's final save follows it
    saves = s["trainer.checkpoint"]
    assert _within(saves[0], steps[1]) and saves[-1][0] >= steps[1][1]


def _op_names(lowered) -> tuple[str, set]:
    """A lowered program's module name and its operations' name stacks
    (the ``op_name`` a profile reports as the operation's ``tf_op``)."""
    hlo = lowered.as_text(dialect="hlo", debug_info=True)
    module = re.match(r"HloModule (\w+)", hlo).group(1)
    return module, set(re.findall(r'op_name="([^"]+)"', hlo))


def _any(names, *parts) -> bool:
    return any(all(p in n for p in parts) for n in names)


def test_decode_step_scopes_and_module_names(engine):
    module, names = _op_names(engine._step_fn.lower(
        engine.params, engine.registry.bank, engine._state))
    assert module == "jit_decode_step"
    for scope in ("q_proj/adapted_dense", "down_proj/adapted_dense",
                  "attn.decode", "sample"):
        assert _any(names, scope), scope
    module, names = _op_names(engine._merged_step_fn.lower(
        engine.params, engine._state))
    assert module == "jit_decode_step_merged"
    assert _any(names, "q_proj/") and _any(names, "attn.decode")
    assert not _any(names, "adapted_dense")     # merged weights: no adapter
    module, names = _op_names(engine._prefill_fns[16].lower(
        engine.params, engine.registry.bank, engine._state,
        np.zeros((1, 16), np.int32), 9, 0, 0, 4))
    assert module == "jit_prefill_p16"
    assert _any(names, "adapted_dense") and not _any(names, "attn.decode")


def test_train_step_scopes_hold_backward_and_recompute():
    """The custom_vjp backwards of the Pallas adapter ops run under the
    forward's ``adapted_dense`` scope, and ``remat`` tags the recomputed
    forward: the finetune readers find both."""
    cfg = dataclasses.replace(get_config(ARCH, "smoke"), remat="full")
    peft, opt = _peft("pallas"), adamw(constant(1e-3))
    state = init_state(jax.random.PRNGKey(0), cfg, peft, opt)
    batch = {"tokens": np.zeros((2, 16), np.int32),
             "labels": np.zeros((2, 16), np.int32)}
    _, names = _op_names(jax.jit(make_train_step(cfg, peft, opt)).lower(
        state, batch))
    # the dx kernel runs in the backward only
    assert _any(names, "adapted_dense", "reflect_gemm_dx_pallas")
    assert _any(names, "rematted_computation", "adapted_dense")
