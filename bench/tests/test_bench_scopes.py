"""The program's own spans and device scopes as a TPU v5e records them,
read with the benchmark's decoder of operation metadata
(``bench/xplane.py``).

Two recorded traces: ``tpu-probe.xplane.pb`` (a bank kernel call and a
matmul under ``jit(<lambda>)``, three times) and ``tpu-scopes.xplane.pb``
(``record_scopes.py``: a two-layer ETHER model at d 256 admits one
request whose tenant is swapped into the bank, takes three bank decode
steps, then two ``Trainer`` steps under ``remat=full``)."""

import os

import pytest
from jax.profiler import ProfileData

from bench import trace as tr
from bench import xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
PROBE = os.path.join(DATA, "tpu-probe.xplane.pb")
SCOPES = os.path.join(DATA, "tpu-scopes.xplane.pb")
DEVICE = "/device:TPU:0"
SPAN_PREFIXES = ("bench.", "engine.", "registry.", "trainer.")


def _on_device(name: str) -> bool:
    return name.startswith("/device:TPU:")


def test_probe_scopes_from_event_metadata():
    ops = xplane.op_scopes(PROBE, _on_device)[(DEVICE, "XLA Ops")]
    assert len(ops) == 21 and all(ops)         # every op event has one
    assert ops.count("jit(<lambda>)/jit(householder_gemm_batched_pallas)"
                     "/pallas_call") == 3
    assert ops.count("jit(<lambda>)/dot_general") == 6
    # the asynchronous copies carry no name stack: their program's name
    assert ops.count("jit__lambda") == 6


def test_probe_events_carry_no_stats_to_match():
    """On a TPU an op's ``tf_op`` and category live in its event's
    metadata, so ``trace.OP_STATS`` finds none of them on the event."""
    t = tr.load(PROBE)
    assert t.ops and all(o.meta == "" for o in t.ops)


@pytest.fixture(scope="module")
def chip():
    """The scopes trace as (ops, spans, modules): each XLA op as
    (kind, start s, end s, scope) on the device clock, each host span as
    a ``trace.Span``, each program as its (start, end)."""
    scopes = xplane.op_scopes(SCOPES, _on_device)
    ops, spans, modules = [], [], []
    for plane in ProfileData.from_file(SCOPES).planes:
        for line in plane.lines:
            events = list(line.events)
            if plane.name == DEVICE and line.name == "XLA Ops":
                ops += [(tr.op_kind(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, sc)
                        for e, sc in zip(events, scopes[(DEVICE, "XLA Ops")])]
            elif plane.name == DEVICE and line.name == "XLA Modules":
                modules += [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in events]
            elif plane.name.startswith("/host:"):
                spans += [tr.Span(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9,
                                  dict(e.stats))
                          for e in events
                          if e.name.startswith(SPAN_PREFIXES)]
    assert len(scopes[(DEVICE, "XLA Ops")]) == len(ops)
    return ops, sorted(spans, key=lambda s: s.start), modules


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _inside(spans, outer, name):
    return [s for s in _named(spans, name)
            if outer.start <= s.start and s.end <= outer.end]


def test_program_spans_nest_with_their_stats(chip):
    _, spans, _ = chip
    (admit,) = _named(spans, "engine.admit")
    assert admit.stats == {"rid": 0, "tenant": 3, "prompt_len": 40,
                           "bucket": 64}
    (acquire,) = _inside(spans, admit, "registry.acquire")
    assert acquire.stats == {"tenant": 3, "hit": 0}
    (swap,) = _inside(spans, acquire, "registry.swap")
    assert swap.stats == {"slot": 3}
    (prefill,) = _inside(spans, admit, "engine.prefill")
    assert prefill.stats == {"bucket": 64} and prefill.start >= acquire.end
    steps = _named(spans, "engine.step")
    assert [s.stats for s in steps] == [
        {"ordinal": n, "active": 1, "tier": 0, "tenants": 1}
        for n in range(3)]
    assert [len(_inside(spans, s, "engine.step.fetch")) for s in steps] == \
        [1, 1, 1]
    fits = _named(spans, "trainer.step")
    assert [s.stats for s in fits] == [{"step": 1}, {"step": 2}]
    for child in ("trainer.batch", "trainer.fetch"):
        assert [len(_inside(spans, s, child)) for s in fits] == [1, 1]


def test_scopes_name_the_layers(chip):
    ops, _, _ = chip
    assert all(sc for *_, sc in ops)
    names = ("householder_gemm_batched_pallas", "householder_gemm_pallas",
             "reflect_gemm_dx_pallas")
    kernels = [(k, sc) for k, *_, sc in ops if k in names]
    assert {k for k, _ in kernels} == set(names)
    # every kernel call, forward, recomputed or backward, is adapter work
    assert all("_proj/adapted_dense/" in sc and sc.endswith("/pallas_call")
               for _, sc in kernels)
    scopes = {sc for *_, sc in ops}
    for needle in ("jit(decode_step)/", "jit(prefill_p64)/", "jit(step)/",
                   "q_proj/adapted_dense", "down_proj/adapted_dense",
                   "attn.decode", "jit(decode_step)/sample/",
                   "rematted_computation", "transpose(jvp())"):
        assert any(needle in sc for sc in scopes), needle
    assert "jit_registry_swap" in scopes          # a program's own copies
    assert not any("counted" in sc for sc in scopes)


def test_programs_run_inside_the_spans_that_launch_them(chip):
    """Once the device clock is aligned on the benchmark's own spans
    (``trace.clock_offset``), each engine step's, prefill's and trainer
    step's operations lie inside that program span, and only its own."""
    ops, spans, modules = chip
    shift = tr.clock_offset(spans, modules)
    for name, program, n in (("engine.step", "decode_step", 3),
                             ("engine.prefill", "prefill_p64", 1),
                             ("trainer.step", "step", 2)):
        found = _named(spans, name)
        assert len(found) == n
        for span in found:
            inside = [sc for _, a, _, sc in ops
                      if span.start <= a + shift < span.end]
            assert inside and all(sc.startswith(
                (f"jit({program})", f"jit_{program}")) for sc in inside), name
