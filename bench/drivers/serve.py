"""Serving driver: closed-loop clients over the system's continuous
batching engine (``ServeEngine.admit`` / ``ServeEngine.step`` over an
``AdapterRegistry``, the objects ``launch/serve.py --trace`` builds).

Set-up makes the weights and the tenant adapters from the seed, builds
the engine, compiles every prompt bucket of the mix, the decode step and
the registry's programs (``ServeEngine.warmup``), starts one client per
decode slot and runs the loop ``ramp_seconds`` so the window opens on a
busy engine.  The window then runs ``--seconds``: each client sends its
next request as soon as its last one completes.  Timestamps are the
engine's own (seconds since process start).

End-to-end metrics, over the window [t0, t1):

* ``tok_s``: output tokens emitted inside the window, over its length;
* ``setup_s``: process start to window start.

The loop is closed, every slot busy all through the window, so the
system runs at its capacity and its tails swing with the smallest
change; they are per-layer metrics (``bench/metrics/*.closed.py``):
the 95th percentile, over the requests sent inside the window, of first
token minus sending (requests still waiting at the close are admitted
after it, and their wait counts), and over the requests completed
inside the window, of (finish - first token) / (tokens - 1).

After the window, and after the device's peak memory is read and the
engine is freed, a sample of the completed requests drawn from the seed
(the longest one and others up to ``check.requests`` and
``check.min_tokens`` served tokens) goes through the plain float32
reference (``bench/model.py`` over the configuration's architecture
module): the widest gap by which a served token's reference logit lies
below the reference's best is compared with the configuration's limit.
With ``Run.control`` the control (the reference one precision below the
configuration's) is put in the program's place: the compared gap is
that of the token it puts first at each served position, and the
program's own gap is a reading beside it.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
from typing import Optional

import numpy as np

from bench import harness, model, system
from bench.traffic import Traffic


@dataclasses.dataclass
class StepRec:
    n: int
    t_start: float
    t_end: float
    ctx_lens: tuple           # live positions of each active sequence
    tenants: int              # distinct tenants among them
    tier: str                 # "bank" or "merged"
    traced: bool


@dataclasses.dataclass
class Layer:
    """What the per-layer readers read (``bench/metrics/*.py``)."""
    cfg: dict
    peak: Optional[dict]
    t0: float
    t1: float
    requests: list            # requests sent inside the window
    steps: list               # StepRec of steps started inside the window
    tier_tokens: dict         # bank/merged decode tokens inside the window
    ttft_ms: list             # first token - sending, requests sent inside
    tpot_ms: list             # per-token gap, requests completed inside


class Loop:
    """The closed loop: one client per slot, requests from ``Traffic``."""

    def __init__(self, engine, traffic: Traffic, clients: int):
        self.engine, self.traffic = engine, traffic
        self.next_j = [0] * clients
        self.pending: list = []
        self.sent: list = []
        self.steps: list = []
        self.emitted: list = []   # (time, tokens)
        self.tracing = False
        self._rid = 0

    def now(self) -> float:
        return self.engine._now()

    def send(self, client: int) -> None:
        from repro.serving.scheduler import Request
        j = self.next_j[client]
        self.next_j[client] += 1
        tenant, prompt, gen = self.traffic.request(client, j)
        req = Request(rid=self._rid, tenant_id=tenant, prompt=prompt,
                      max_new_tokens=gen, arrival_s=self.now())
        req.client = client
        self._rid += 1
        self.pending.append(req)
        self.sent.append(req)

    def finished(self, reqs, accept: bool) -> None:
        for req in reqs:
            if req.finish_s is None:
                req.finish_s = self.now()
            if accept:
                self.send(req.client)

    def admit(self, accept: bool = True) -> None:
        import jax
        from repro.serving.scheduler import AdmissionError
        while self.pending and self.engine.n_free:
            req = self.pending.pop(0)
            try:
                with jax.profiler.TraceAnnotation("bench.admit"):
                    done = self.engine.admit(req)
            except AdmissionError as e:
                req.error = e
                self.finished([req], accept)
                continue
            if req.first_token_s is not None:
                self.emitted.append((req.first_token_s, 1))
            self.finished(done, accept)

    def step(self, accept: bool = True) -> None:
        import jax
        inflight = self.engine.inflight()
        ctx = tuple(len(r.prompt) + len(r.tokens) for r in inflight.values())
        before = sum(len(r.tokens) for r in inflight.values())
        tiers = dict(self.engine.tier_stats)
        n = len(self.steps)
        t_start = self.now()
        with jax.profiler.TraceAnnotation("bench.step", n=n):
            done = self.engine.step()
        t_end = self.now()
        tier = ("merged" if self.engine.tier_stats["merged_steps"]
                > tiers["merged_steps"] else "bank")
        self.steps.append(StepRec(
            n, t_start, t_end, ctx,
            len({r.tenant_id for r in inflight.values()}), tier,
            self.tracing))
        self.emitted.append(
            (t_end, sum(len(r.tokens) for r in inflight.values()) - before))
        self.finished(done, accept)

    def run_until(self, t_end: float, on_step=None) -> None:
        while self.now() < t_end:
            self.admit()
            if self.engine.n_active:
                self.step()
            if on_step is not None:
                on_step()


def run(r: harness.Run) -> harness.Outcome:
    import jax
    from repro.core import execute
    from repro.serving import AdapterRegistry, ServeEngine
    cfg, mix = r.cfg, r.mix
    execute.reset_counters()
    sv = cfg["serving"]
    block = harness.arch(cfg)
    mcfg, peft = block.model_config(cfg), system.peft_config(cfg)
    weights = block.make_weights(cfg, r.seed)
    adapters = model.adapter_fn(cfg, r.seed)
    system.check_layout(cfg, mcfg, peft, weights, adapters(0))
    registry = AdapterRegistry(weights, peft, sv["bank_rows"],
                               n_tenants=sv["tenants"], init_fn=adapters,
                               merged_capacity=sv["merged_capacity"])
    engine = ServeEngine(mcfg, weights, registry, peft, slots=sv["slots"],
                         prompt_buckets=mix["prompt_buckets"],
                         max_new_tokens=mix["output"]["max"],
                         max_len=sv["max_len"])
    engine.start_clock(r.t_proc)
    engine.warmup()
    traffic = Traffic(mix, sv["tenants"], cfg["vocab_size"], sv["slots"],
                      r.seed)
    loop = Loop(engine, traffic, sv["slots"])
    for c in range(sv["slots"]):
        loop.send(c)
    loop.run_until(loop.now() + mix["ramp_seconds"])

    # -- the window ---------------------------------------------------
    log_dir = window_span = None
    if r.trace:
        log_dir = harness.trace_dir()
        jax.profiler.start_trace(log_dir)
        window_span = jax.profiler.TraceAnnotation("bench.window")
        window_span.__enter__()
        loop.tracing = True
    compiles0 = harness.Compiles.count()
    t0 = loop.now()
    t1 = t0 + r.seconds
    t_trace_end = t0 + min(r.seconds, mix["trace_seconds"])
    tier0 = dict(engine.tier_stats)
    n_sent0 = len(loop.sent)

    def stop_trace():
        if loop.tracing and loop.now() >= t_trace_end:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            loop.tracing = False

    loop.run_until(t1, on_step=stop_trace)
    compiles = harness.Compiles.count() - compiles0
    tier1 = dict(engine.tier_stats)
    if loop.tracing:
        loop.tracing = False
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    # requests sent before the close still get their first token
    window_reqs = [q for q in loop.sent[n_sent0:] if q.arrival_s < t1]
    while any(q.first_token_s is None and q.error is None
              for q in window_reqs):
        loop.admit(accept=False)
        if any(q.first_token_s is None and q.error is None
               for q in window_reqs) and engine.n_active:
            loop.step(accept=False)

    ttft = [(q.first_token_s - q.arrival_s) * 1e3 for q in window_reqs
            if q.first_token_s is not None]
    done = [q for q in loop.sent if q.finish_s is not None
            and q.error is None and t0 <= q.finish_s < t1]
    tpot = [(q.finish_s - q.first_token_s) / (len(q.tokens) - 1) * 1e3
            for q in done if len(q.tokens) > 1]
    tokens = sum(k for t, k in loop.emitted if t0 <= t < t1)
    e2e = {"tok_s": tokens / r.seconds, "setup_s": t0}
    layer = Layer(cfg=cfg, peak=r.peak, t0=t0, t1=t1, requests=window_reqs,
                  steps=[s for s in loop.steps if t0 <= s.t_start < t1],
                  tier_tokens={k: tier1[k] - tier0[k]
                               for k in ("bank_tokens", "merged_tokens")},
                  ttft_ms=ttft, tpot_ms=tpot)
    mem = harness.memory_peak(r.cell["chips"])

    # -- after the window: free the engine, then the reference --------
    sample = _sample(done, mix["check"], r.seed)
    del loop, engine, registry
    gc.collect()
    checks, readings = _check(cfg, mix, weights, adapters, sample,
                              r.control, sv["max_len"])
    checks["window_compiles"] = (float(compiles), 0.0)
    readings["adapter_ops"] = execute.counters()
    trace = None
    if log_dir is not None:
        from bench import trace as tr
        trace = tr.load(tr.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    failed = sum(1 for q in window_reqs if q.error is not None)
    return harness.Outcome(e2e=e2e, layer=layer, checks=checks,
                           attempted=len(window_reqs), failed=failed,
                           memory_peak=mem, trace=trace, readings=readings)


def _sample(done: list, spec: dict, seed: int) -> list:
    """The longest completed request, then others drawn from the seed,
    until ``spec['requests']`` requests or ``spec['min_tokens']`` served
    tokens."""
    if not done:
        return []
    done = sorted(done, key=lambda q: q.rid)
    longest = max(done, key=lambda q: (len(q.tokens), -q.rid))
    rest = [q for q in done if q is not longest]
    order = np.random.default_rng(int(seed) % (1 << 64)).permutation(
        len(rest))
    out = [longest]
    for i in order:
        if (len(out) >= spec["requests"]
                or sum(len(q.tokens) for q in out) >= spec["min_tokens"]):
            break
        out.append(rest[i])
    return out


def _check(cfg, mix, weights, adapters, sample, control: bool,
           length: int) -> tuple[dict, dict]:
    """Widest gap of the served tokens under the float32 reference; with
    ``control``, of the control's own first choices in their place."""
    import jax.numpy as jnp
    limit = cfg["check"]["widest_gap"]
    max_new = mix["output"]["max"]
    if not sample:
        return {"served_requests_checked": (0.0, -1.0)}, {}
    fns = {"program": model.served_gaps(cfg)}
    if control:
        fns["control"] = model.served_gaps(cfg, mix["control"])
    widest = {k: 0.0 for k in fns}
    for q in sample:
        seq = np.zeros(length, np.int32)
        full = np.concatenate([q.prompt, np.asarray(q.tokens, np.int32)])
        seq[:len(full)] = full
        n = len(q.tokens)
        served = np.zeros(max_new, np.int32)
        served[:n] = q.tokens
        pos = np.zeros(max_new, np.int32)
        pos[:n] = len(q.prompt) - 1 + np.arange(n)
        ad = adapters(q.tenant_id)
        for k, fn in fns.items():
            gap, _ = fn(weights, ad, jnp.asarray(seq), jnp.asarray(served),
                        jnp.asarray(pos))
            widest[k] = max(widest[k], float(np.max(np.asarray(gap)[:n])))
    readings = {"checked_requests": len(sample),
                "checked_tokens": sum(len(q.tokens) for q in sample)}
    if control:
        readings["program_widest_gap"] = widest["program"]
        return {"widest_gap": (widest["control"], limit)}, readings
    return {"widest_gap": (widest["program"], limit)}, readings
