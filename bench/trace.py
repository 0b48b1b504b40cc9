"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

What it reads:

* device operations: the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane (all lines of the plane where it has no such
  line), each with its name, start and duration;
* host spans: events on the host plane whose name starts with
  ``bench.``, the benchmark's own ``TraceAnnotation`` spans, with their
  integer stats (``n`` numbers a step).

All times are seconds on the host's clock of the trace.  The device's
clock is synchronised with it only to about a millisecond, so the
reduction shifts every device time by the offset that puts the most
device programs (``XLA Modules`` events) wholly inside the host spans
that wait for them (``SYNC_SPANS``: each ends on a device fetch).  Busy
time is the union of a device's operation intervals; idle gaps are the
holes in that union inside the traced window, each named by the
innermost ``bench.`` span around its midpoint ("outside" where there is
none).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Optional

SPAN_PREFIX = "bench."
# host spans that end on a device fetch: the programs they launch run
# inside them
SYNC_SPANS = ("bench.step", "bench.admit")


# string stats of a device operation kept for matching it by name: the
# framework op (JAX's name stack, which holds the jitted wrapper of a
# kernel) and the HLO category
OP_STATS = ("tf_op", "hlo_category", "long_name")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    dur: float
    device: int
    meta: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    stats: tuple


@dataclasses.dataclass
class Trace:
    ops: list                 # Op, sorted by start
    spans: list               # Span, sorted by start
    devices: int

    def window(self) -> tuple[float, float]:
        """The traced window: the ``bench.window`` span where the trace
        has one, else from the first to the last bench span or device
        operation."""
        marked = self.spans_named(SPAN_PREFIX + "window")
        if marked:
            return marked[0].start, marked[0].end
        pts = [s.start for s in self.spans] + [o.start for o in self.ops]
        ends = [s.end for s in self.spans] + [o.end for o in self.ops]
        return (min(pts), max(ends)) if pts else (0.0, 0.0)

    def ops_in(self, start: float, end: float, device: Optional[int] = None):
        """Operations that start inside [start, end)."""
        return [o for o in self.ops if start <= o.start < end
                and (device is None or o.device == device)]

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _device_index(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, modules, devices = [], [], [], set()
    for plane in pd.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            lines = list(plane.lines)
            for line in lines:
                if line.name == "XLA Modules":
                    modules += [(e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                                for e in line.events]
            named = [ln for ln in lines if ln.name == "XLA Ops"]
            for line in named or lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        meta = " ".join(str(v)[:300] for k, v in e.stats
                                        if k in OP_STATS)
                        ops.append(Op(e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9, dev, meta))
                        devices.add(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        st = tuple(sorted(
                            (k, v) for k, v in e.stats
                            if isinstance(v, (int, float))))
                        spans.append(Span(e.name, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns) * 1e-9,
                                          st))
    spans.sort(key=lambda s: s.start)
    shift = clock_offset(spans, modules)
    ops = sorted((dataclasses.replace(o, start=o.start + shift)
                  for o in ops), key=lambda o: o.start)
    return Trace(ops, spans, len(devices))


def clock_offset(spans, modules, window: float = 0.05) -> float:
    """Seconds to add to device times so that the most device programs
    lie wholly inside a synchronous host span; 0 with nothing to align.
    Candidates put a program's start on a span's start or its end on the
    span's end; the best ones bound an interval, whose middle is taken."""
    sync = sorted((s.start, s.end) for s in spans if s.name in SYNC_SPANS)
    if not sync or not modules:
        return 0.0
    import bisect
    starts = [a for a, _ in sync]

    def inside(delta):
        n = 0
        for a, b in modules:
            i = bisect.bisect_right(starts, a + delta) - 1
            n += i >= 0 and b + delta <= sync[i][1]
        return n

    mods = sorted(modules)
    mstarts = [a for a, _ in mods]
    cands = set()
    for a, b in sync:
        lo = bisect.bisect_left(mstarts, a - window)
        hi = bisect.bisect_right(mstarts, b)
        for m in mods[lo:hi][:4]:
            cands.add(a - m[0])
            cands.add(b - m[1])
    if not cands:
        return 0.0
    scored = [(inside(d), d) for d in sorted(cands)]
    best = max(n for n, _ in scored)
    top = [d for n, d in scored if n == best]
    return (top[0] + top[-1]) / 2


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(trace: Trace, start: float, end: float,
         device: Optional[int] = None) -> float:
    """Seconds inside [start, end) in which an operation ran."""
    ivs = [(max(o.start, start), min(o.end, end)) for o in trace.ops
           if (device is None or o.device == device)
           and o.end > start and o.start < end]
    return sum(e - s for s, e in union(ivs))


def busy_per_device(trace: Trace) -> tuple[float, float]:
    """(busy seconds averaged over the devices, traced window seconds)."""
    lo, hi = trace.window()
    devs = sorted({o.device for o in trace.ops})
    if not devs:
        return 0.0, hi - lo
    return sum(busy(trace, lo, hi, d) for d in devs) / len(devs), hi - lo


def op_kind(name: str) -> str:
    """An operation's short name without its instance number, so that
    instances of one kind add up: the TPU names an operation by its HLO
    text (``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``)."""
    m = re.match(r"%?([^\s=(]+)", name)
    short = m.group(1) if m else name
    return re.sub(r"[.:_-]?\d+$", "", short) or short


def self_times(trace: Trace) -> list[tuple[Op, float]]:
    """Each operation with its self time: its duration less the time of
    the operations nested inside it (a ``while`` loop's body runs as
    operations of its own within the loop's interval)."""
    out = []
    for dev in sorted({o.device for o in trace.ops}):
        stack: list[list] = []           # [op, self time]
        for o in (x for x in trace.ops if x.device == dev):
            while stack and o.start >= stack[-1][0].end:
                out.append(tuple(stack.pop()))
            if stack:
                stack[-1][1] -= min(o.end, stack[-1][0].end) - o.start
            stack.append([o, o.dur])
        out += [tuple(s) for s in stack]
    return out


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` kinds of device operation with most self time, in
    seconds summed over devices and instances."""
    tot: dict[str, float] = defaultdict(float)
    for o, t in self_times(trace):
        tot[op_kind(o.name)] += t
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def _innermost(spans, t: float) -> str:
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.start >= best.start):
            best = s
    return best.name if best is not None else "outside"


def idle_gaps(trace: Trace, k: int = 10,
              device: Optional[int] = None) -> list[list]:
    """Idle seconds of one device (the first by default) inside the
    traced window, summed by what the host was doing, the ``k`` largest."""
    devs = sorted({o.device for o in trace.ops})
    if not devs:
        return []
    dev = devs[0] if device is None else device
    lo, hi = trace.window()
    ivs = union((o.start, o.end) for o in trace.ops if o.device == dev)
    gaps, t = [], lo
    for s, e in ivs:
        if s >= hi:
            break
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    tot: dict[str, float] = defaultdict(float)
    for s, e in gaps:
        tot[_innermost(trace.spans, (s + e) / 2)] += e - s
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def matches(op: Op, patterns) -> bool:
    """True when an operation's own short name (not the operands of its
    HLO text) or its kept stats contain any of ``patterns``."""
    low = (op_kind(op.name) + " " + op.meta).lower()
    return any(p.lower() in low for p in patterns)


def step_spans(trace: Trace, name: str = SPAN_PREFIX + "step") -> dict:
    """The spans called ``name``, by their ``n`` stat."""
    return {dict(s.stats).get("n"): s for s in trace.spans_named(name)}
