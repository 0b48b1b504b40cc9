"""Execution-backend dispatch layer + multi-tenant AdapterBank.

Covers the DESIGN.md §3 backend registry (jnp / pallas / auto selection,
trace counters, adapted_dense equivalence) and the §2 multi-tenant path
(batched kernel parity, bank round-trip on stacked weights, tenant ids
through prefill/decode_step).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import execute
from repro.core.peft import (AdapterBank, init_adapter_bank, init_adapters,
                             merge_params)
from repro.core.transforms import (PEFTConfig, adapted_dense, init_adapter,
                                   reflect_activation,
                                   reflect_activation_batched)

RNG = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Registry / selection
# ---------------------------------------------------------------------------

def test_registry_has_both_backends_for_every_ether_op():
    for op in ("ether_reflect", "householder_gemm", "ether_merge",
               "ether_reflect_batched", "etherplus_gemm",
               "householder_gemm_batched", "etherplus_reflect_batched",
               "etherplus_merge"):
        assert set(execute.available(op)) == {"jnp", "pallas"}, op


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        PEFTConfig(method="ether", backend="cuda")
    with pytest.raises(ValueError):
        execute.dispatch("ether_reflect", "cuda",
                         jnp.ones((4, 8)), jnp.ones((2, 4)))


def test_auto_selects_pallas_on_tileable_jnp_on_odd(monkeypatch):
    x_good = jnp.ones((128, 256))
    w_good = jnp.ones((256, 128))
    u_good = jnp.ones((8, 32))
    assert execute.selected_backend(
        "householder_gemm", "auto", x_good, w_good, u_good) == "pallas"
    # an odd f wider than a whole-row tile tiles nowhere
    w_wide = jnp.ones((256, 600))
    assert execute.selected_backend(
        "householder_gemm", "auto", x_good, w_wide, u_good) == "jnp"
    # on a TPU an odd f dimension cannot tile the MXU lanes at all
    w_odd = jnp.ones((256, 130))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert execute.selected_backend(
        "householder_gemm", "auto", x_good, w_odd, u_good) == "jnp"
    assert execute.selected_backend(
        "householder_gemm", "auto", x_good, w_good, u_good) == "pallas"


def test_dispatch_counters_track_trace_counts():
    execute.reset_counters()
    x = jax.random.normal(RNG, (64, 128))
    u = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    execute.dispatch("ether_reflect", "auto", x, u)
    execute.dispatch("ether_reflect", "jnp", x, u)
    c = execute.counters()
    assert c.get("ether_reflect.pallas") == 1
    assert c.get("ether_reflect.jnp") == 1


# ---------------------------------------------------------------------------
# adapted_dense backend equivalence (acceptance: pallas ≡ jnp ≤ 1e-5)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["activation", "weight"])
def test_adapted_dense_backend_equivalence(mode):
    d, f, n = 256, 128, 8
    a = init_adapter(RNG, "ether", d, f,
                     PEFTConfig(method="ether", n_blocks=n))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, d))
    W = jax.random.normal(jax.random.PRNGKey(2), (d, f))
    b = jax.random.normal(jax.random.PRNGKey(3), (f,))
    outs = {}
    for backend in ("jnp", "pallas", "auto"):
        cfg = PEFTConfig(method="ether", n_blocks=n, mode=mode,
                         backend=backend)
        outs[backend] = np.asarray(adapted_dense(x, W, b, a, cfg))
    np.testing.assert_allclose(outs["pallas"], outs["jnp"], atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(outs["auto"], outs["jnp"], atol=1e-5,
                               rtol=1e-5)


def test_adapted_dense_auto_executes_pallas_on_tileable_shapes():
    """Acceptance: with backend='auto' on tileable shapes the Pallas path
    demonstrably runs (trace counter)."""
    d, f, n = 256, 128, 8
    a = init_adapter(RNG, "ether", d, f,
                     PEFTConfig(method="ether", n_blocks=n))
    x = jax.random.normal(jax.random.PRNGKey(1), (128, d))
    W = jax.random.normal(jax.random.PRNGKey(2), (d, f))
    cfg = PEFTConfig(method="ether", n_blocks=n, backend="auto")
    execute.reset_counters()
    y = jax.jit(lambda x: adapted_dense(x, W, None, a, cfg))(x)
    assert execute.counters().get("householder_gemm.pallas", 0) >= 1
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(reflect_activation(x, a["u"]) @ W),
        atol=1e-4, rtol=1e-4)


def test_adapted_dense_auto_falls_back_on_odd_shapes():
    # f=600: neither 128-aligned nor a whole-row tile (<= 512)
    d, f, n = 30, 600, 5
    a = init_adapter(RNG, "ether", d, f,
                     PEFTConfig(method="ether", n_blocks=n))
    x = jax.random.normal(jax.random.PRNGKey(1), (7, d))
    W = jax.random.normal(jax.random.PRNGKey(2), (d, f))
    cfg = PEFTConfig(method="ether", n_blocks=n, backend="auto")
    execute.reset_counters()
    y = adapted_dense(x, W, None, a, cfg)
    c = execute.counters()
    assert c.get("householder_gemm.jnp", 0) >= 1
    assert c.get("householder_gemm.pallas", 0) == 0
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(reflect_activation(x, a["u"]) @ W),
        atol=1e-5)


def test_gradients_flow_through_pallas_backend():
    """Interpret-mode Pallas kernels are differentiable — training can
    run on the kernel path too."""
    d, f, n = 128, 128, 4
    a = init_adapter(RNG, "ether", d, f,
                     PEFTConfig(method="ether", n_blocks=n))
    x = jax.random.normal(jax.random.PRNGKey(1), (128, d))
    W = jax.random.normal(jax.random.PRNGKey(2), (d, f))

    def loss(u, backend):
        cfg = PEFTConfig(method="ether", n_blocks=n, backend=backend)
        return jnp.sum(adapted_dense(x, W, None, {"u": u}, cfg) ** 2)

    g_jnp = jax.grad(lambda u: loss(u, "jnp"))(a["u"])
    g_pal = jax.grad(lambda u: loss(u, "pallas"))(a["u"])
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_jnp),
                               atol=5e-2, rtol=1e-4)


# ---------------------------------------------------------------------------
# Multi-tenant bank through adapted_dense
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas", "auto"])
def test_bank_adapted_dense_matches_per_row(backend):
    d, f, n, A, B, S = 256, 128, 8, 6, 4, 16
    bank = jax.random.normal(RNG, (A, n, d // n))
    ids = jnp.array([0, 5, 2, 2], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d))
    W = jax.random.normal(jax.random.PRNGKey(2), (d, f))
    cfg = PEFTConfig(method="ether", n_blocks=n, backend=backend)
    y = adapted_dense(x, W, None, {"u": bank, "ids": ids}, cfg)
    for b in range(B):
        exp = reflect_activation(x[b], bank[ids[b]]) @ W
        np.testing.assert_allclose(np.asarray(y[b]), np.asarray(exp),
                                   atol=1e-4, rtol=1e-4)


def test_bank_requires_activation_mode_and_batched_x():
    d, n = 16, 4
    bank = jax.random.normal(RNG, (3, n, d // n))
    ids = jnp.zeros((2,), jnp.int32)
    W = jnp.eye(d)
    adapter = {"u": bank, "ids": ids}
    with pytest.raises(ValueError):
        adapted_dense(jnp.ones((2, 3, d)), W, None, adapter,
                      PEFTConfig(method="ether", n_blocks=n, mode="weight"))
    with pytest.raises(ValueError):   # batch dim mismatch with ids
        adapted_dense(jnp.ones((5, 3, d)), W, None, adapter,
                      PEFTConfig(method="ether", n_blocks=n))


# ---------------------------------------------------------------------------
# AdapterBank round-trip / request trees
# ---------------------------------------------------------------------------

def _moe_like_params(L=3, E=4, d=16, f=24):
    k = jax.random.PRNGKey(7)
    return {
        "units": {"pos0": {
            "mlp": {"gate_proj": {"kernel": jax.random.normal(
                k, (L, E, d, f))}},
            "mixer": {"q_proj": {"kernel": jax.random.normal(
                jax.random.fold_in(k, 1), (L, d, d))}},
        }},
        "head": {"out_proj": {"kernel": jax.random.normal(
            jax.random.fold_in(k, 2), (d, d))}},
    }


def test_adapter_bank_round_trip_stacked_moe_weights():
    """stack → select(i) returns tenant i's tree exactly, including
    (L, E, d, f) MoE expert banks and unstacked leaves."""
    params = _moe_like_params()
    cfg = PEFTConfig(method="ether", n_blocks=4,
                     targets="q_proj+gate_proj+out_proj")
    trees = [init_adapters(jax.random.PRNGKey(i), params, cfg)
             for i in range(5)]
    bank = AdapterBank.stack(trees, params, cfg)
    assert bank.tenants == 5
    # tenant axis sits AFTER the stack dims
    g = bank.tree["units"]["pos0"]["mlp"]["gate_proj"]["u"]
    assert g.shape[:3] == (3, 4, 5)                 # (L, E, N, ...)
    q = bank.tree["units"]["pos0"]["mixer"]["q_proj"]["u"]
    assert q.shape[:2] == (3, 5)                    # (L, N, ...)
    o = bank.tree["head"]["out_proj"]["u"]
    assert o.shape[0] == 5                          # (N, ...)
    for i in (0, 2, 4):
        sel = bank.select(i)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), sel, trees[i])


def test_adapter_bank_request_broadcasts_ids_over_stacks():
    params = _moe_like_params()
    cfg = PEFTConfig(method="ether", n_blocks=4,
                     targets="q_proj+gate_proj+out_proj")
    bank = init_adapter_bank(RNG, params, cfg, tenants=4)
    ids = jnp.array([1, 3], jnp.int32)
    req = bank.request(ids)
    assert req["units"]["pos0"]["mixer"]["q_proj"]["ids"].shape == (3, 2)
    assert req["units"]["pos0"]["mlp"]["gate_proj"]["ids"].shape == (3, 4, 2)
    assert req["head"]["out_proj"]["ids"].shape == (2,)


def test_adapter_bank_rejects_non_ether():
    params = _moe_like_params()
    cfg = PEFTConfig(method="lora", targets="q_proj")
    with pytest.raises(ValueError):
        init_adapter_bank(RNG, params, cfg, tenants=2)


def test_adapter_bank_is_a_pytree():
    params = _moe_like_params()
    cfg = PEFTConfig(method="ether", n_blocks=4, targets="q_proj")
    bank = init_adapter_bank(RNG, params, cfg, tenants=3)
    leaves, treedef = jax.tree_util.tree_flatten(bank)
    bank2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(bank2, AdapterBank)
    assert bank2.tenants == 3 and bank2.stack_ndims == bank.stack_ndims


# ---------------------------------------------------------------------------
# Tenant ids through the serving entry points
# ---------------------------------------------------------------------------

def test_prefill_decode_with_adapter_bank_matches_single_tenant():
    """Bank serving row b ≡ serving the whole batch with tenant ids[b]'s
    plain adapter tree (per-request isolation end-to-end)."""
    from repro.configs import get_config, peft_targets
    from repro.models import decode_step, init_model, prefill

    cfg = get_config("smollm-360m", "smoke")
    peft = PEFTConfig(method="ether", n_blocks=4,
                      targets=peft_targets("smollm-360m"))
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


def test_bank_without_ids_raises():
    from repro.configs import get_config, peft_targets
    from repro.models import init_model, prefill

    cfg = get_config("smollm-360m", "smoke")
    peft = PEFTConfig(method="ether", n_blocks=4,
                      targets=peft_targets("smollm-360m"))
    params = init_model(RNG, cfg)
    bank = init_adapter_bank(RNG, params, peft, 2)
    with pytest.raises(ValueError):
        prefill(params, bank, {"tokens": jnp.zeros((1, 4), jnp.int32)},
                cfg, peft)


def test_merge_params_on_selected_tenant():
    """Zero-latency deployment of one tenant from the bank: merged
    weights reproduce that tenant's adapted forward."""
    from repro.configs import get_config, peft_targets
    from repro.models import init_model, prefill

    cfg = get_config("smollm-360m", "smoke")
    peft = PEFTConfig(method="ether", n_blocks=4,
                      targets=peft_targets("smollm-360m"))
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


# ---------------------------------------------------------------------------
# Batched reflection fix (gather before normalize)
# ---------------------------------------------------------------------------

def test_batched_reflection_gathers_before_normalizing():
    """The O(B·d) path must equal per-row gather+normalize even when the
    bank holds far more adapters than the batch references."""
    d, n, A, B, S = 24, 4, 50, 3, 5
    bank = jax.random.normal(RNG, (A, n, d // n)) * 10.0
    ids = jnp.array([49, 0, 7], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d))
    out = reflect_activation_batched(x, bank, ids)
    for b in range(B):
        exp = reflect_activation(x[b], bank[ids[b]])
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(exp),
                                   atol=1e-5)
