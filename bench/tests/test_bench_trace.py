"""The trace reduction on a small trace recorded on a TPU v5e: three
``bench.step`` spans, each around one bank reflect-GEMM kernel call
(16 x 2048 x 2048) and a small matmul, and three ``bench.admit`` spans
around a 512 x 512 XLA matmul, all inside ``bench.window``."""

import os

import pytest

from bench import trace as tr

PATH = os.path.join(os.path.dirname(__file__), "data",
                    "tpu-probe.xplane.pb")


@pytest.fixture(scope="module")
def t():
    return tr.load(PATH)


def test_planes_read(t):
    assert t.devices == 1
    assert [s.name for s in t.spans].count("bench.step") == 3
    assert sorted(tr.step_spans(t)) == [0, 1, 2]
    assert all(o.device == 0 and o.dur > 0 for o in t.ops)


def test_kernel_found_by_name(t):
    k = [o for o in t.ops if tr.matches(o, ["householder_gemm_batched"])]
    assert len(k) == 3
    # one call at 16 x 2048 x 2048 took about 1.24 ms on the chip
    assert all(1.0e-3 < o.dur < 1.5e-3 for o in k)
    assert tr.top_ops(t)[0][0] == "householder_gemm_batched_pallas"


def test_busy_and_idle(t):
    busy, window = tr.busy_per_device(t)
    lo, hi = t.window()
    assert window == pytest.approx(hi - lo) and 0.015 < window < 0.025
    assert 0 < busy < window
    gaps = tr.idle_gaps(t)
    assert {n for n, _ in gaps} <= {"bench.step", "bench.admit",
                                    "bench.window", "outside"}
    assert sum(s for _, s in gaps) == pytest.approx(window - busy)


def test_union_and_kinds():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.op_kind("%fusion.12 = bf16[2]{0} fusion(x)") == "fusion"
    assert tr.op_kind("copy-done.3") == "copy-done"


def test_device_clock_aligned_to_host_spans(t):
    # each step's kernel runs inside its bench.step span once the device
    # clock is shifted (raw device times lead the host's by ~1-2 ms)
    spans = tr.step_spans(t)
    k = [o for o in t.ops if tr.matches(o, ["householder_gemm_batched"])]
    for n, op in enumerate(k):
        assert spans[n].start <= op.start and op.end <= spans[n].end
    assert tr.clock_offset([], [(0.0, 1.0)]) == 0.0
    span = tr.Span("bench.step", 1.0, 2.0, ())
    # a program 10 ms before its span: it fits for shifts of 0.01-1.005 s
    assert tr.clock_offset([span], [(0.99, 0.995)]) == \
        pytest.approx((0.01 + 1.005) / 2)


def test_self_time_of_nested_ops():
    ops = [tr.Op("while", 0.0, 10.0, 0), tr.Op("a", 1.0, 2.0, 0),
           tr.Op("b", 4.0, 3.0, 0), tr.Op("c", 12.0, 1.0, 0)]
    got = {o.name: s for o, s in tr.self_times(tr.Trace(ops, [], 1))}
    assert got == {"while": 5.0, "a": 2.0, "b": 3.0, "c": 1.0}
