"""Hand-derived Pallas backward tier (*_bwd ops).

Gradient oracles for every backward kernel: jax.grad of the dispatched
op against jax.grad of the jnp reference on odd token counts,
non-divisor dims, bf16/f32, and the bank ops with duplicate tenant ids
(gradient scatter-accumulation).  Plus the registry contract — every
forward op has a first-class ``<op>_bwd`` with both backends — and the
counter honesty the acceptance criteria demand: a jax.grad through
``adapted_dense`` at supported shapes increments *Pallas* bwd counters
with zero ref-AD fallbacks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import execute
from repro.core.peft import init_adapter_bank
from repro.core.transforms import PEFTConfig, adapted_dense, init_adapter
from repro.kernels import ops, ref  # noqa: F401 — populates the registry

RNG = jax.random.PRNGKey(0)

FWD_OPS = ("ether_reflect", "householder_gemm", "ether_merge",
           "ether_reflect_batched", "etherplus_gemm",
           "householder_gemm_batched", "etherplus_reflect_batched",
           "etherplus_merge")

GTOL = dict(atol=5e-2, rtol=1e-3)       # f32 GEMM accumulation noise


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


def _assert_grads_close(gp, gj, tol):
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol),
        gp, gj)


def _assert_grads_close_frob(gp, gj, rel=2e-2):
    """bf16 comparisons: the kernels reflect in f32 while the bf16 jnp
    ref rounds every intermediate, so elementwise tolerances measure the
    REFERENCE's rounding; relative Frobenius error is the honest metric."""
    def chk(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        err = np.linalg.norm(a - b) / (np.linalg.norm(b) + 1.0)
        assert err < rel, f"relative grad error {err:.4f} >= {rel}"
    jax.tree_util.tree_map(chk, gp, gj)


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------

def test_every_forward_op_has_bwd_with_both_backends():
    for op in FWD_OPS:
        assert set(execute.available(op + "_bwd")) == {"jnp", "pallas"}, op


def test_counters_phase_split():
    execute.reset_counters()
    x = _rand(RNG, (64, 128))
    u = _rand(jax.random.fold_in(RNG, 1), (4, 32))
    g = _rand(jax.random.fold_in(RNG, 2), (64, 128))
    execute.dispatch("ether_reflect", "pallas", x, u)
    execute.dispatch("ether_reflect_bwd", "pallas", x, u, g)
    assert execute.counters("fwd") == {"ether_reflect.pallas": 1}
    assert execute.counters("bwd") == {"ether_reflect_bwd.pallas": 1}
    assert set(execute.counters()) == {"ether_reflect.pallas",
                                       "ether_reflect_bwd.pallas"}
    with pytest.raises(ValueError):
        execute.counters("sideways")


# ---------------------------------------------------------------------------
# Gradient oracles: dispatched pallas grad ≡ jnp-ref grad
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d,n", [(64, 128, 4), (7, 96, 8),     # odd t
                                   (300, 384, 12), (1, 256, 8)])
def test_ether_reflect_grad_oracle(t, d, n, dtype):
    x = _rand(RNG, (t, d), dtype)
    u = _rand(jax.random.fold_in(RNG, 1), (n, d // n))
    # linear probe, NOT sum(y**2): reflections preserve norms, so a
    # quadratic loss has zero true gradient and compares rounding noise
    m = _rand(jax.random.fold_in(RNG, 7), (t, d))

    def loss(u, backend):
        return jnp.sum(execute.dispatch("ether_reflect", backend, x, u)
                       .astype(jnp.float32) * m)

    gj = jax.grad(lambda u: loss(u, "jnp"))(u)
    gp = jax.grad(lambda u: loss(u, "pallas"))(u)
    if dtype == jnp.float32:
        _assert_grads_close(gp, gj, GTOL)
    else:
        _assert_grads_close_frob(gp, gj)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d,f,n", [(128, 128, 128, 4),
                                     (64, 256, 384, 8),
                                     (96, 96, 96, 8)])   # odd tokens
def test_householder_gemm_grad_oracle(t, d, f, n, dtype):
    x = _rand(RNG, (t, d), dtype)
    w = _rand(jax.random.fold_in(RNG, 1), (d, f))
    u = _rand(jax.random.fold_in(RNG, 2), (n, d // n))

    m = _rand(jax.random.fold_in(RNG, 7), (t, f))

    def loss(lv, backend):
        y = execute.dispatch("householder_gemm", backend, x, lv["w"],
                             lv["u"])
        return jnp.sum(y.astype(jnp.float32) * m)

    leaves = {"w": w, "u": u}
    gj = jax.grad(lambda lv: loss(lv, "jnp"))(leaves)
    gp = jax.grad(lambda lv: loss(lv, "pallas"))(leaves)
    if dtype == jnp.float32:
        _assert_grads_close(gp, gj, GTOL)
    else:
        _assert_grads_close_frob(gp, gj)


@pytest.mark.parametrize("two_sided", [False, True])
def test_etherplus_gemm_grad_oracle(two_sided):
    t, d, f, n, n2 = 64, 128, 384, 4, 12
    x = _rand(RNG, (t, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, f))
    leaves = {"u1": _rand(jax.random.fold_in(RNG, 2), (n, d // n)),
              "v1": _rand(jax.random.fold_in(RNG, 3), (n, d // n))}
    if two_sided:
        leaves["u2"] = _rand(jax.random.fold_in(RNG, 4), (n2, f // n2))
        leaves["v2"] = _rand(jax.random.fold_in(RNG, 5), (n2, f // n2))

    def loss(lv, backend):
        y = execute.dispatch("etherplus_gemm", backend, x, w,
                             lv["u1"], lv["v1"], lv.get("u2"),
                             lv.get("v2"))
        return jnp.sum(y ** 2)

    gj = jax.grad(lambda lv: loss(lv, "jnp"))(leaves)
    gp = jax.grad(lambda lv: loss(lv, "pallas"))(leaves)
    _assert_grads_close(gp, gj, GTOL)


@pytest.mark.parametrize("d,f", [(128, 512), (96, 96), (256, 384)])
def test_merge_grad_oracles(d, f):
    n, n2 = 4, 8 if f % 8 == 0 else 4
    w = _rand(RNG, (d, f))
    u = _rand(jax.random.fold_in(RNG, 1), (n, d // n))
    m = _rand(jax.random.fold_in(RNG, 7), (d, f))   # linear probe (see
    g1 = jax.grad(lambda u: jnp.sum(                # reflect oracle)
        execute.dispatch("ether_merge", "jnp", w, u) * m))(u)
    g2 = jax.grad(lambda u: jnp.sum(
        execute.dispatch("ether_merge", "pallas", w, u) * m))(u)
    _assert_grads_close(g2, g1, GTOL)

    leaves = {"u1": u, "v1": _rand(jax.random.fold_in(RNG, 2),
                                   (n, d // n)),
              "u2": _rand(jax.random.fold_in(RNG, 3), (n2, f // n2)),
              "v2": _rand(jax.random.fold_in(RNG, 4), (n2, f // n2))}

    def loss(lv, backend):
        return jnp.sum(execute.dispatch(
            "etherplus_merge", backend, w, lv["u1"], lv["v1"], lv["u2"],
            lv["v2"]) ** 2)

    gj = jax.grad(lambda lv: loss(lv, "jnp"))(leaves)
    gp = jax.grad(lambda lv: loss(lv, "pallas"))(leaves)
    _assert_grads_close(gp, gj, GTOL)


# ---------------------------------------------------------------------------
# Bank ops: duplicate tenant ids must scatter-ACCUMULATE
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op,extra_bank", [
    ("ether_reflect_batched", False),
    ("householder_gemm_batched", False),
    ("etherplus_reflect_batched", True),
])
@pytest.mark.parametrize("S", [1, 16, 7])                 # odd S too
def test_bank_grad_duplicate_ids(op, extra_bank, S):
    B, d, f, n, A = 4, 128, 128, 4, 6
    ids = jnp.array([5, 2, 5, 5], jnp.int32)              # heavy repeats
    x = _rand(RNG, (B, S, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, f))
    bank = _rand(jax.random.fold_in(RNG, 2), (A, n, d // n))
    vbank = _rand(jax.random.fold_in(RNG, 3), (A, n, d // n))

    m = _rand(jax.random.fold_in(RNG, 7), (B, S, f))

    def loss(lv, backend):
        if op == "ether_reflect_batched":
            y = execute.dispatch(op, backend, x, lv["u"], ids)
        elif op == "householder_gemm_batched":
            y = execute.dispatch(op, backend, x, w, lv["u"], ids)
        else:
            y = execute.dispatch(op, backend, x, lv["u"], lv["v"], ids)
        return jnp.sum(y * m)

    leaves = {"u": bank, "v": vbank} if extra_bank else {"u": bank}
    gj = jax.grad(lambda lv: loss(lv, "jnp"))(leaves)
    gp = jax.grad(lambda lv: loss(lv, "pallas"))(leaves)
    _assert_grads_close(gp, gj, GTOL)
    # rows no request references get exactly zero gradient (isolation);
    # the thrice-referenced row 5 must NOT equal a single-reference one
    for lv in (gj, gp):
        np.testing.assert_allclose(np.asarray(lv["u"][0]), 0.0, atol=1e-6)
        assert float(jnp.abs(lv["u"][5]).max()) > 0


def test_bank_grad_accumulates_not_overwrites():
    """ids=[a, a] gradient == 2 × ids=[a] gradient for identical rows."""
    B, S, d, n, A = 2, 8, 96, 8, 3
    bank = _rand(RNG, (A, n, d // n))
    x_row = _rand(jax.random.fold_in(RNG, 1), (1, S, d))
    x2 = jnp.concatenate([x_row, x_row], axis=0)

    m_row = _rand(jax.random.fold_in(RNG, 7), (1, S, d))

    def loss(b, x, ids, m):
        return jnp.sum(execute.dispatch("ether_reflect_batched", "pallas",
                                        x, b, ids) * m)

    m2 = jnp.concatenate([m_row, m_row], axis=0)
    g_twice = jax.grad(loss)(bank, x2, jnp.array([1, 1], jnp.int32), m2)
    g_once = jax.grad(loss)(bank, x_row, jnp.array([1], jnp.int32), m_row)
    np.testing.assert_allclose(np.asarray(g_twice), 2 * np.asarray(g_once),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Direct *_bwd dispatch equivalence + fallback honesty
# ---------------------------------------------------------------------------

def test_bwd_dispatch_backends_agree():
    t, d, f, n = 64, 128, 128, 4
    x = _rand(RNG, (t, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, f))
    u = _rand(jax.random.fold_in(RNG, 2), (n, d // n))
    g = _rand(jax.random.fold_in(RNG, 3), (t, f))
    out_j = execute.dispatch("householder_gemm_bwd", "jnp", x, w, u, g)
    out_p = execute.dispatch("householder_gemm_bwd", "pallas", x, w, u, g)
    for a, b, name in zip(out_j, out_p, ("dx", "dw", "du")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3, err_msg=name)


def test_bwd_non_tiling_shapes_fall_back_truthfully():
    """An odd f (600: not 128-aligned, wider than a whole-row tile)
    tiles nothing: `auto` resolves the backward to ref-AD and counts it
    as *_bwd.jnp — never a silent wrong kernel."""
    t, d, n = 7, 30, 5
    x = _rand(RNG, (t, d))
    w = _rand(jax.random.fold_in(RNG, 1), (d, 600))
    u = _rand(jax.random.fold_in(RNG, 2), (n, d // n))

    def loss(u, backend):
        return jnp.sum(execute.dispatch("householder_gemm", backend, x, w,
                                        u) ** 2)

    execute.reset_counters()
    gp = jax.grad(lambda u: loss(u, "pallas"))(u)
    gj = jax.grad(lambda u: loss(u, "jnp"))(u)
    c = execute.counters("bwd")
    assert c.get("householder_gemm_bwd.jnp", 0) >= 1
    assert c.get("householder_gemm_bwd.pallas", 0) == 0
    _assert_grads_close(gp, gj, GTOL)


# ---------------------------------------------------------------------------
# Acceptance: jax.grad through adapted_dense hits Pallas both directions
# ---------------------------------------------------------------------------

def _grad_through_adapted_dense(method, bank_mode, backend):
    d, f, n, B, S, A = 128, 128, 4, 3, 16, 5
    cfg = PEFTConfig(method=method, n_blocks=n, backend=backend)
    W = _rand(jax.random.fold_in(RNG, 9), (d, f))
    if bank_mode:
        bank = init_adapter_bank(RNG, {"q_proj": {"kernel": W}},
                                 PEFTConfig(method=method, n_blocks=n,
                                            targets="q_proj"), tenants=A)
        ids = jnp.array([4, 0, 4], jnp.int32)
        adapter = bank.request(ids)["q_proj"]
        x = _rand(jax.random.fold_in(RNG, 1), (B, S, d))
    else:
        adapter = init_adapter(RNG, method, d, f, cfg)
        x = _rand(jax.random.fold_in(RNG, 1), (64, d))

    def loss(a):
        full = dict(adapter, **a)
        return jnp.sum(adapted_dense(x, W, None, full, cfg) ** 2)

    trainable = {k: v for k, v in adapter.items() if k != "ids"}
    return jax.jit(jax.grad(loss))(trainable)


@pytest.mark.parametrize("method", ["ether", "etherplus"])
@pytest.mark.parametrize("bank_mode", [False, True])
def test_grad_through_adapted_dense_is_kernel_backed(method, bank_mode):
    """Acceptance: jax.grad of adapted_dense (ether and etherplus,
    single-tenant and bank) increments Pallas bwd counters with zero
    ref-AD fallbacks at supported shapes, and matches the jnp-ref
    gradient."""
    execute.reset_counters()
    gp = _grad_through_adapted_dense(method, bank_mode, "auto")
    bwd = execute.counters("bwd")
    assert sum(v for k, v in bwd.items() if k.endswith(".pallas")) >= 1, bwd
    assert not any(k.endswith(".jnp") or k.endswith("pallas_fallback")
                   for k in bwd), bwd
    gj = _grad_through_adapted_dense(method, bank_mode, "jnp")
    _assert_grads_close(gp, gj, GTOL)
