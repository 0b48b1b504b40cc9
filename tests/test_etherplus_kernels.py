"""Fused ETHER+ / batched-GEMM kernel tier (DESIGN.md §3).

Property-style oracle sweeps (seeded; real hypothesis when installed,
the deterministic fallback shim otherwise) for ``etherplus_gemm``,
``householder_gemm_batched`` and ``etherplus_reflect_batched`` against
their ``kernels/ref.py`` oracles — forward AND backward — plus registry
wiring, fallback counters, the ETHER+ AdapterBank serving path, and the
kernel-backed ``etherplus_merge`` absorption.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:                                  # pragma: no cover
    from _hypothesis_fallback import hypothesis, st

from repro.core import execute
from repro.core.peft import AdapterBank, init_adapter_bank, merge_params
from repro.core.transforms import (PEFTConfig, adapted_dense,
                                   etherplus_activation,
                                   etherplus_activation_batched,
                                   init_adapter)
from repro.kernels import ops, ref
from repro.kernels.etherplus_gemm import etherplus_gemm_pallas
from repro.kernels.etherplus_reflect_batched import (
    etherplus_reflect_batched_pallas)
from repro.kernels.householder_gemm_batched import (
    householder_gemm_batched_pallas)

RNG = jax.random.PRNGKey(0)

TOL = dict(atol=2e-3, rtol=2e-3)        # f32 GEMM accumulation-order noise
RTOL = dict(atol=1e-5, rtol=1e-5)       # pure reflections, no GEMM


def _rand(key, shape, scale=1.0):
    return jax.random.normal(key, shape) * scale


# ---------------------------------------------------------------------------
# etherplus_gemm — fused rank-2 reflect + GEMM (+ two-sided epilogue)
# ---------------------------------------------------------------------------

@hypothesis.settings(deadline=None, max_examples=6)
@hypothesis.given(t=st.sampled_from([4, 64, 128]),
                  d=st.sampled_from([96, 128, 256]),
                  n=st.integers(1, 8),
                  seed=st.integers(0, 2**16))
def test_etherplus_gemm_one_sided_oracle(t, d, n, seed):
    while d % n:
        n -= 1
    k = jax.random.fold_in(RNG, seed)
    x = _rand(k, (t, d))
    w = _rand(jax.random.fold_in(k, 1), (d, d))
    u1 = _rand(jax.random.fold_in(k, 2), (n, d // n))
    v1 = _rand(jax.random.fold_in(k, 3), (n, d // n))
    out = etherplus_gemm_pallas(x, w, u1, v1, interpret=True)
    exp = ref.ref_etherplus_gemm(x, w, u1, v1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), **TOL)


@hypothesis.settings(deadline=None, max_examples=6)
@hypothesis.given(t=st.sampled_from([4, 128]),
                  shapes=st.sampled_from([(96, 96, 8, 8), (128, 384, 4, 12),
                                          (256, 128, 8, 4)]),
                  seed=st.integers(0, 2**16))
def test_etherplus_gemm_two_sided_oracle(t, shapes, seed):
    """The fused H̃⁺ epilogue must equal reflect-after-GEMM exactly."""
    d, f, n, n2 = shapes
    k = jax.random.fold_in(RNG, seed)
    x = _rand(k, (t, d))
    w = _rand(jax.random.fold_in(k, 1), (d, f))
    u1 = _rand(jax.random.fold_in(k, 2), (n, d // n))
    v1 = _rand(jax.random.fold_in(k, 3), (n, d // n))
    u2 = _rand(jax.random.fold_in(k, 4), (n2, f // n2))
    v2 = _rand(jax.random.fold_in(k, 5), (n2, f // n2))
    out = ops.etherplus_gemm(x, w, u1, v1, u2, v2)
    exp = ref.ref_etherplus_gemm(x, w, u1, v1, u2, v2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), **TOL)


def test_etherplus_gemm_grad_matches_jnp():
    """custom_vjp backward (jnp-ref AD) ≡ XLA AD of the reference, for
    every trainable leaf of a two-sided adapter."""
    d, f, n = 128, 128, 4
    x = _rand(RNG, (64, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, f))
    leaves = {name: _rand(jax.random.fold_in(RNG, 2 + i),
                          (n, (d if i < 2 else f) // n))
              for i, name in enumerate(("u1", "v1", "u2", "v2"))}

    def loss(lv, backend):
        y = execute.dispatch("etherplus_gemm", backend, x, w,
                             lv["u1"], lv["v1"], lv["u2"], lv["v2"])
        return jnp.sum(y ** 2)

    g_jnp = jax.grad(lambda lv: loss(lv, "jnp"))(leaves)
    g_pal = jax.grad(lambda lv: loss(lv, "pallas"))(leaves)
    for name in leaves:
        np.testing.assert_allclose(np.asarray(g_pal[name]),
                                   np.asarray(g_jnp[name]),
                                   atol=5e-2, rtol=1e-3)


def test_etherplus_gemm_identity_at_init():
    """v=u ⇒ H⁺=I (the paper's init): the fused kernel must preserve it."""
    d, n = 128, 4
    x = _rand(RNG, (8, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, d))
    u = _rand(jax.random.fold_in(RNG, 2), (n, d // n))
    out = ops.etherplus_gemm(x, w, u, u, u, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# householder_gemm_batched — fused tenant-gather + reflect + GEMM
# ---------------------------------------------------------------------------

@hypothesis.settings(deadline=None, max_examples=6)
@hypothesis.given(B=st.integers(1, 5), S=st.sampled_from([1, 16, 64]),
                  shapes=st.sampled_from([(96, 96, 8), (128, 256, 4),
                                          (256, 128, 8)]),
                  A=st.integers(1, 9), seed=st.integers(0, 2**16))
def test_householder_gemm_batched_oracle(B, S, shapes, A, seed):
    d, f, n = shapes
    k = jax.random.fold_in(RNG, seed)
    x = _rand(k, (B, S, d))
    w = _rand(jax.random.fold_in(k, 1), (d, f))
    bank = _rand(jax.random.fold_in(k, 2), (A, n, d // n))
    ids = jax.random.randint(jax.random.fold_in(k, 3), (B,), 0, A,
                             jnp.int32)
    out = householder_gemm_batched_pallas(x, w, bank, ids, interpret=True)
    exp = ref.ref_householder_gemm_batched(x, w, bank, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), **TOL)


# ids per slot: all one tenant, all different, repeats with unreferenced
# rows between them (bank of 9 rows)
FOLD_IDS = {"same": lambda b: [4] * b,
            "distinct": lambda b: [8 - i for i in range(b)],
            "gaps": lambda b: [(0, 5, 0, 8, 5, 8, 0, 5)[i] for i in range(b)]}


@pytest.mark.parametrize("shapes", [(256, 384, 4), (512, 256, 2)],
                         ids=["db64", "db256"])
@pytest.mark.parametrize("pattern", sorted(FOLD_IDS))
@pytest.mark.parametrize("B", [1, 3, 8])
def test_householder_gemm_batched_folded_oracle(B, pattern, shapes):
    """S == 1 (decode) takes the folded grid: through ``ops`` at its
    decode tiles, and at the smallest legal tiles (several F- and
    K-tiles, so the reflected-row scratch is filled and re-read)."""
    d, f, n = shapes
    db = d // n
    k = jax.random.fold_in(RNG, 1000 * B + d)
    x = _rand(k, (B, 1, d))
    w = _rand(jax.random.fold_in(k, 1), (d, f))
    bank = _rand(jax.random.fold_in(k, 2), (9, n, db))
    ids = jnp.array(FOLD_IDS[pattern](B), jnp.int32)
    exp = ref.ref_householder_gemm_batched(x, w, bank, ids)
    execute.reset_counters()
    out = ops.householder_gemm_batched(x, w, bank, ids, interpret=True)
    assert execute.counters() == {"householder_gemm_batched.pallas_rows": 1}
    small = householder_gemm_batched_pallas(
        x, w, bank, ids, block_f=128, block_k=max(db, 128), interpret=True)
    for y in (out, small):
        assert y.shape == (B, 1, f)
        np.testing.assert_allclose(np.asarray(y), np.asarray(exp), **TOL)


def test_householder_gemm_batched_folded_row_matches_per_sequence():
    """A row decoded alone (S == 1, folded grid) equals the same row
    inside an S == 16 call (per-sequence grid): one computation."""
    B, S, d, f, n = 3, 16, 256, 384, 4
    x = _rand(RNG, (B, S, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, f))
    bank = _rand(jax.random.fold_in(RNG, 2), (9, n, d // n))
    ids = jnp.array([5, 0, 5], jnp.int32)
    execute.reset_counters()
    whole = ops.householder_gemm_batched(x, w, bank, ids, interpret=True)
    assert execute.counters() == {}                  # S > 1: not folded
    one = ops.householder_gemm_batched(x[:, 7:8], w, bank, ids,
                                       interpret=True)
    assert execute.counters() == {"householder_gemm_batched.pallas_rows": 1}
    np.testing.assert_allclose(np.asarray(one[:, 0]),
                               np.asarray(whole[:, 7]), atol=1e-4,
                               rtol=1e-5)


def _batched_grads(S):
    B, d, f, n, A = 3, 128, 128, 4, 5
    x = _rand(RNG, (B, S, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, f))
    bank = _rand(jax.random.fold_in(RNG, 2), (A, n, d // n))
    ids = jnp.array([4, 0, 2], jnp.int32)

    def loss(b, backend):
        return jnp.sum(execute.dispatch("householder_gemm_batched",
                                        backend, x, w, b, ids) ** 2)

    execute.reset_counters()
    g_jnp = jax.grad(lambda b: loss(b, "jnp"))(bank)
    g_pal = jax.grad(lambda b: loss(b, "pallas"))(bank)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_jnp),
                               atol=5e-2, rtol=1e-3)
    # rows no request references must get zero gradient (isolation)
    np.testing.assert_allclose(np.asarray(g_jnp[1]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_pal[1]), 0.0, atol=1e-6)
    return execute.counters()


def test_householder_gemm_batched_grad_matches_jnp():
    c = _batched_grads(16)
    assert "householder_gemm_batched.pallas_rows" not in c


def test_householder_gemm_batched_grad_matches_jnp_s1():
    """S == 1: the folded forward under the per-sequence backward."""
    c = _batched_grads(1)
    assert c["householder_gemm_batched.pallas_rows"] == 1
    assert c["householder_gemm_batched_bwd.pallas"] == 1


# ---------------------------------------------------------------------------
# etherplus_reflect_batched — per-tenant rank-2 bank reflect
# ---------------------------------------------------------------------------

@hypothesis.settings(deadline=None, max_examples=6)
@hypothesis.given(B=st.integers(1, 5), S=st.sampled_from([1, 7, 32]),
                  d=st.sampled_from([96, 128, 384]), n=st.integers(1, 8),
                  A=st.integers(1, 9), seed=st.integers(0, 2**16))
def test_etherplus_reflect_batched_oracle(B, S, d, n, A, seed):
    while d % n:
        n -= 1
    k = jax.random.fold_in(RNG, seed)
    x = _rand(k, (B, S, d))
    ub = _rand(jax.random.fold_in(k, 1), (A, n, d // n))
    vb = _rand(jax.random.fold_in(k, 2), (A, n, d // n))
    ids = jax.random.randint(jax.random.fold_in(k, 3), (B,), 0, A,
                             jnp.int32)
    out = etherplus_reflect_batched_pallas(x, ub, vb, ids, interpret=True)
    exp = ref.ref_etherplus_reflect_batched(x, ub, vb, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), **RTOL)
    # and the core.transforms jnp formulation agrees with the oracle
    np.testing.assert_allclose(
        np.asarray(etherplus_activation_batched(x, ub, vb, ids)),
        np.asarray(exp), **RTOL)


def test_etherplus_reflect_batched_grad_matches_jnp():
    B, S, d, n, A = 2, 8, 96, 8, 4
    x = _rand(RNG, (B, S, d))
    ub = _rand(jax.random.fold_in(RNG, 1), (A, n, d // n))
    vb = _rand(jax.random.fold_in(RNG, 2), (A, n, d // n))
    ids = jnp.array([3, 1], jnp.int32)

    def loss(banks, backend):
        return jnp.sum(execute.dispatch(
            "etherplus_reflect_batched", backend, x,
            banks["u"], banks["v"], ids) ** 2)

    g_jnp = jax.grad(lambda b: loss(b, "jnp"))({"u": ub, "v": vb})
    g_pal = jax.grad(lambda b: loss(b, "pallas"))({"u": ub, "v": vb})
    for kk in ("u", "v"):
        np.testing.assert_allclose(np.asarray(g_pal[kk]),
                                   np.asarray(g_jnp[kk]),
                                   atol=5e-2, rtol=1e-3)


# ---------------------------------------------------------------------------
# Fallback honesty: non-tiling shapes under auto / explicit pallas
# ---------------------------------------------------------------------------

def test_non_tiling_shapes_fall_back_with_truthful_counters():
    """t=300 tokens tiles neither 128 nor <=256: `auto` selects jnp, and
    an explicit pallas request is counted as `pallas_fallback` (the
    wrapper falls back to the ref internally) — never as a live kernel."""
    d, f, n = 96, 96, 8
    x = _rand(RNG, (300, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, f))
    u1 = _rand(jax.random.fold_in(RNG, 2), (n, d // n))
    v1 = _rand(jax.random.fold_in(RNG, 3), (n, d // n))
    assert not execute.supports("etherplus_gemm", x, w, u1, v1, None, None)
    execute.reset_counters()
    y_auto = execute.dispatch("etherplus_gemm", "auto", x, w, u1, v1,
                              None, None)
    y_pal = execute.dispatch("etherplus_gemm", "pallas", x, w, u1, v1,
                             None, None)
    c = execute.counters()
    assert c.get("etherplus_gemm.jnp", 0) == 1
    assert c.get("etherplus_gemm.pallas_fallback", 0) == 1
    assert c.get("etherplus_gemm.pallas", 0) == 0
    exp = ref.ref_etherplus_gemm(x, w, u1, v1)
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(exp), **TOL)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(exp), **TOL)


def test_batched_non_tiling_falls_back():
    """S=300 (not 128-tileable) batched ops must fall back, correctly."""
    B, S, d, n, A = 2, 300, 96, 8, 3
    x = _rand(RNG, (B, S, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, d))
    ub = _rand(jax.random.fold_in(RNG, 2), (A, n, d // n))
    vb = _rand(jax.random.fold_in(RNG, 3), (A, n, d // n))
    ids = jnp.array([2, 0], jnp.int32)
    execute.reset_counters()
    y = execute.dispatch("householder_gemm_batched", "auto", x, w, ub, ids)
    r = execute.dispatch("etherplus_reflect_batched", "pallas", x, ub, vb,
                         ids)
    c = execute.counters()
    assert c.get("householder_gemm_batched.jnp", 0) == 1
    assert c.get("etherplus_reflect_batched.pallas_fallback", 0) == 1
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.ref_householder_gemm_batched(
            x, w, ub, ids)), **TOL)
    np.testing.assert_allclose(
        np.asarray(r), np.asarray(ref.ref_etherplus_reflect_batched(
            x, ub, vb, ids)), **RTOL)


def test_direct_kernel_call_odd_tokens_no_crash():
    """Satellite: ether_reflect_pallas must not assert on odd t (shrinks
    block_t to the largest divisor); same guard in etherplus_gemm."""
    d, n = 96, 8
    for t in (7, 13, 300):
        x = _rand(RNG, (t, d))
        u = _rand(jax.random.fold_in(RNG, 1), (n, d // n))
        from repro.kernels.ether_reflect import ether_reflect_pallas
        out = ether_reflect_pallas(x, u, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.ref_ether_reflect(x, u)),
                                   **RTOL)
        w = _rand(jax.random.fold_in(RNG, 2), (d, d))
        v = _rand(jax.random.fold_in(RNG, 3), (n, d // n))
        out = etherplus_gemm_pallas(x, w, u, v, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.ref_etherplus_gemm(x, w, u, v)),
            **TOL)


# ---------------------------------------------------------------------------
# etherplus_merge — kernel-backed absorption
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("two_sided", [False, True])
def test_etherplus_merge_oracle_and_dispatch(two_sided):
    d, f, n, n2 = 128, 384, 4, 12
    w = _rand(RNG, (d, f))
    u1 = _rand(jax.random.fold_in(RNG, 1), (n, d // n))
    v1 = _rand(jax.random.fold_in(RNG, 2), (n, d // n))
    u2 = _rand(jax.random.fold_in(RNG, 3), (n2, f // n2)) if two_sided \
        else None
    v2 = _rand(jax.random.fold_in(RNG, 4), (n2, f // n2)) if two_sided \
        else None
    exp = ref.ref_etherplus_merge(w, u1, v1, u2, v2)
    for backend in ("jnp", "pallas", "auto"):
        out = execute.dispatch("etherplus_merge", backend, w, u1, v1,
                               u2, v2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=1e-4, rtol=1e-4)


def test_merge_weight_etherplus_is_kernel_backed():
    """Satellite: merged-deployment absorption routes through
    core.execute and the pallas path actually fires."""
    d, f, n = 96, 96, 8
    cfg = PEFTConfig(method="etherplus", n_blocks=n, backend="auto")
    a = init_adapter(RNG, "etherplus", d, f, cfg)
    a = {kk: vv + 0.1 * _rand(jax.random.fold_in(RNG, i), vv.shape)
         for i, (kk, vv) in enumerate(sorted(a.items()))}
    from repro.core.transforms import merge_weight
    w = _rand(jax.random.fold_in(RNG, 9), (d, f))
    execute.reset_counters()
    wm = merge_weight(w, a, cfg)
    assert execute.counters().get("etherplus_merge.pallas", 0) == 1
    x = _rand(jax.random.fold_in(RNG, 10), (4, d))
    exp = adapted_dense(x, w, None, a, cfg)
    np.testing.assert_allclose(np.asarray(x @ wm), np.asarray(exp),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# ETHER+ AdapterBank serving (end to end)
# ---------------------------------------------------------------------------

def _bank_cfg(backend="auto"):
    return PEFTConfig(method="etherplus", n_blocks=8, targets="q_proj",
                      backend=backend)


@pytest.mark.parametrize("backend", ["jnp", "pallas", "auto"])
def test_etherplus_bank_adapted_dense_matches_per_row(backend):
    d, f, B, S, A = 96, 256, 4, 16, 6
    W = _rand(RNG, (d, f))
    cfg = _bank_cfg(backend)
    bank = init_adapter_bank(jax.random.fold_in(RNG, 1),
                             {"q_proj": {"kernel": W}}, cfg, tenants=A)
    ids = jnp.array([0, 5, 2, 2], jnp.int32)
    x = _rand(jax.random.fold_in(RNG, 2), (B, S, d))
    y = adapted_dense(x, W, None, bank.request(ids)["q_proj"], cfg)
    for b in range(B):
        sel = bank.select(int(ids[b]))["q_proj"]
        exp = adapted_dense(x[b], W, None, sel, cfg)
        np.testing.assert_allclose(np.asarray(y[b]), np.asarray(exp),
                                   atol=1e-4, rtol=1e-4)


def test_etherplus_bank_pallas_live_at_serving_shapes():
    """Acceptance: decode-shape (S=1) ETHER+ bank dispatch hits the
    pallas kernels, not the fallback."""
    d, f, B, A = 96, 96, 4, 4
    W = _rand(RNG, (d, f))
    cfg = _bank_cfg("auto")
    bank = init_adapter_bank(jax.random.fold_in(RNG, 1),
                             {"q_proj": {"kernel": W}}, cfg, tenants=A)
    ids = jnp.array([3, 0, 1, 2], jnp.int32)
    x = _rand(jax.random.fold_in(RNG, 2), (B, 1, d))
    execute.reset_counters()
    jax.jit(lambda x: adapted_dense(x, W, None,
                                    bank.request(ids)["q_proj"], cfg))(x)
    c = execute.counters()
    assert c.get("etherplus_reflect_batched.pallas", 0) == 2  # in + out side
    assert c.get("etherplus_reflect_batched.pallas_fallback", 0) == 0


def test_etherplus_bank_prefill_decode_matches_single_tenant():
    from repro.configs import get_config, peft_targets
    from repro.models import decode_step, init_model, prefill

    cfg = get_config("smollm-360m", "smoke")
    peft = PEFTConfig(method="etherplus", n_blocks=4,
                      targets=peft_targets("smollm-360m"), backend="auto")
    params = init_model(RNG, cfg)
    bank = init_adapter_bank(jax.random.fold_in(RNG, 1), params, peft, 3)
    B, P = 2, 8
    tokens = jax.random.randint(jax.random.fold_in(RNG, 2), (B, P), 0,
                                cfg.vocab)
    ids = jnp.array([2, 0], jnp.int32)
    cache, logits = prefill(params, bank, {"tokens": tokens}, cfg, peft,
                            tenant_ids=ids)
    step_tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    logits2, _ = decode_step(params, bank, cache, step_tok, cfg, peft,
                             tenant_ids=ids)
    for b in range(B):
        single = bank.select(int(ids[b]))
        c1, l1 = prefill(params, single, {"tokens": tokens[b:b + 1]},
                         cfg, peft)
        np.testing.assert_allclose(np.asarray(logits[b]),
                                   np.asarray(l1[0]), atol=2e-4, rtol=2e-4)
        l2, _ = decode_step(params, single, c1, step_tok[b:b + 1], cfg,
                            peft)
        np.testing.assert_allclose(np.asarray(logits2[b]),
                                   np.asarray(l2[0]), atol=2e-4, rtol=2e-4)


def test_etherplus_bank_merge_selected_tenant():
    """bank.select(i) + merge_params (kernel-backed etherplus_merge)
    reproduces tenant i's adapted forward with zero-latency weights."""
    from repro.configs import get_config, peft_targets
    from repro.models import init_model, prefill

    cfg = get_config("smollm-360m", "smoke")
    peft = PEFTConfig(method="etherplus", n_blocks=4,
                      targets=peft_targets("smollm-360m"), backend="auto")
    params = init_model(RNG, cfg)
    bank = init_adapter_bank(jax.random.fold_in(RNG, 1), params, peft, 3)
    tokens = jax.random.randint(jax.random.fold_in(RNG, 2), (1, 8), 0,
                                cfg.vocab)
    _, l_adapted = prefill(params, bank.select(1), {"tokens": tokens},
                           cfg, peft)
    merged = merge_params(params, bank.select(1), peft)
    _, l_merged = prefill(merged, None, {"tokens": tokens}, cfg, None)
    np.testing.assert_allclose(np.asarray(l_adapted), np.asarray(l_merged),
                               atol=2e-3, rtol=2e-3)


def test_two_sided_config_with_one_sided_adapter_raises():
    """Config/checkpoint mismatch must fail loudly, not silently serve
    the one-sided transform."""
    d, f, n = 96, 96, 8
    one_sided = PEFTConfig(method="etherplus", n_blocks=n,
                           two_sided=False)
    a = init_adapter(RNG, "etherplus", d, f, one_sided)   # no u2/v2
    x = _rand(jax.random.fold_in(RNG, 1), (4, d))
    W = _rand(jax.random.fold_in(RNG, 2), (d, f))
    two_sided = PEFTConfig(method="etherplus", n_blocks=n)
    with pytest.raises(ValueError, match="u2/v2"):
        adapted_dense(x, W, None, a, two_sided)
    from repro.core.transforms import merge_weight
    with pytest.raises(ValueError, match="u2/v2"):
        merge_weight(W, a, two_sided)
    # matching config serves fine
    y = adapted_dense(x, W, None, a, one_sided)
    assert y.shape == (4, f)


def test_bank_still_rejects_additive_methods():
    W = _rand(RNG, (16, 16))
    cfg = PEFTConfig(method="lora", targets="q_proj")
    with pytest.raises(ValueError):
        AdapterBank.stack([{"q_proj": {"a": W, "b": W}}],
                          {"q_proj": {"kernel": W}}, cfg)


# ---------------------------------------------------------------------------
# Registry coverage for the new tier + bench-suite contract
# ---------------------------------------------------------------------------

def test_new_ops_registered_with_both_backends():
    for op in ("etherplus_gemm", "householder_gemm_batched",
               "etherplus_reflect_batched", "etherplus_merge"):
        assert set(execute.available(op)) == {"jnp", "pallas"}, op


def test_kernels_suite_covers_every_registered_pair():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        from benchmarks import kernels_suite
    except ImportError:
        pytest.skip("benchmarks package not importable")
    finally:
        sys.path.pop(0)
    # iters=1: this asserts the coverage contract, not the timings
    payload = kernels_suite.run_suite(shapes="tiny", iters=1)
    covered = {(e["op"], e["backend"]) for e in payload["entries"]}
    fwd_pairs = {p for p in execute._REGISTRY if not execute.is_bwd_op(p[0])}
    assert covered == fwd_pairs
    # every forward op must have a registered backward with both
    # backends — the train suite (BENCH_train.json) times those rows
    for op, _ in fwd_pairs:
        assert set(execute.available(op + "_bwd")) == {"jnp", "pallas"}, op
