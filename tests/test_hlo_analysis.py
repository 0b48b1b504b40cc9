"""The loop-aware HLO analyzer against ground truth: a scanned matmul
stack where dense FLOPs are known exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo, HloModule


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flops_single_matmul():
    a = jnp.zeros((128, 256))
    b = jnp.zeros((256, 64))
    text = _compile_text(lambda x, y: x @ y, a, b)
    s = analyze_hlo(text)
    assert s["flops"] == pytest.approx(2 * 128 * 256 * 64, rel=1e-6)


def test_flops_scan_counts_trips():
    """lax.scan over L matmuls must count L× the body flops — the whole
    reason cost_analysis() is insufficient (it counts the body once)."""
    L, m, k = 8, 64, 64
    ws = jnp.zeros((L, k, k))
    x = jnp.zeros((m, k))

    def f(x, ws):
        def body(c, w):
            return c @ w, ()
        out, _ = jax.lax.scan(body, x, ws)
        return out

    text = _compile_text(f, x, ws)
    s = analyze_hlo(text)
    assert s["flops"] == pytest.approx(L * 2 * m * k * k, rel=0.01), \
        f"expected {L}x body flops, got ratio " \
        f"{s['flops'] / (2 * m * k * k):.2f}"


def test_flops_nested_scan():
    L1, L2, m, k = 4, 3, 32, 32
    ws = jnp.zeros((L1, L2, k, k))
    x = jnp.zeros((m, k))

    def f(x, ws):
        def outer(c, wrow):
            def inner(c2, w):
                return c2 @ w, ()
            c, _ = jax.lax.scan(inner, c, wrow)
            return c, ()
        out, _ = jax.lax.scan(outer, x, ws)
        return out

    s = analyze_hlo(_compile_text(f, x, ws))
    assert s["flops"] == pytest.approx(L1 * L2 * 2 * m * k * k, rel=0.01)


def test_grad_flops_roughly_3x():
    """Backward of y = x@w ⇒ two extra matmuls (dx, dw): total ≈ 3×."""
    m = k = n = 64
    x = jnp.ones((m, k))
    w = jnp.ones((k, n))

    def loss(x, w):
        return jnp.sum(x @ w)

    fwd = analyze_hlo(_compile_text(lambda x, w: x @ w, x, w))["flops"]
    both = analyze_hlo(_compile_text(jax.grad(loss, argnums=(0, 1)),
                                     x, w))["flops"]
    assert both == pytest.approx(2 * fwd, rel=0.05)  # dx + dw (no fwd out)


def test_collectives_counted_with_trips(subproc):
    """A psum inside a scan on a 4-device mesh: payload must multiply by
    trip count."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_analysis import analyze_hlo
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
w = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)

def f(x, ws):
    def body(c, w):
        return c @ w, ()
    out, _ = jax.lax.scan(body, x, ws)
    return out

sh_x = NamedSharding(mesh, P(None, "data"))
sh_w = NamedSharding(mesh, P(None, "data", None))
text = jax.jit(f, in_shardings=(sh_x, sh_w)).lower(x, w).compile().as_text()
s = analyze_hlo(text)
print("COLL", s["collective_bytes"], s["coll_count"])
assert s["collective_bytes"] > 0
""", devices=4, timeout=300)
    assert "COLL" in out


def test_module_structure_parsing():
    text = _compile_text(lambda x: jnp.sin(x) @ x.T, jnp.zeros((32, 32)))
    m = HloModule(text)
    assert m.entry is not None
    assert m.computations[m.entry]
    assert all(isinstance(v, str) for v in m.shapes.values())
