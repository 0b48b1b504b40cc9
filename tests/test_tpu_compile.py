"""Compile the main-path adapter kernels for a TPU v5e chip at Phi-1.5
widths (d_model=2048, d_ff=8192, n_blocks=32), with no chip attached.

Interpret mode accepts tilings the TPU compiler refuses, so these
compiles are what guard the on-chip serve and train paths: the bank
GEMM (prefill and decode), the bank reflection, the single-tenant fused
GEMM and its backward, and the merge of the promotion hot tier.  The
topology is described inside a fixture (only one process may load the
TPU library, and the tests run under several workers) and the tests
skip when it cannot be described.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

D, FF, N_BLOCKS, BANK = 2048, 8192, 32, 16
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _bank(d):
    return (BANK, N_BLOCKS, d // N_BLOCKS), F32


@pytest.mark.parametrize("rows,seq,d,f", [
    (1, 512, D, FF),        # prefill, up_proj (db = 64)
    (1, 512, FF, D),        # prefill, down_proj (db = 256)
    (8, 1, D, D),           # decode, one token per slot
    (8, 1, FF, D),
])
def test_householder_gemm_batched_compiles(one_chip, rows, seq, d, f):
    from repro.kernels import ops
    _compile(lambda x, w, u, ids: ops.householder_gemm_batched(
        x, w, u, ids, interpret=False), one_chip,
        ((rows, seq, d), BF16), ((d, f), BF16), _bank(d),
        ((rows,), jnp.int32))


def test_ether_reflect_batched_compiles(one_chip):
    from repro.kernels import ops
    _compile(lambda x, u, ids: ops.ether_reflect_batched(
        x, u, ids, interpret=False), one_chip,
        ((8, 128, D), BF16), _bank(D), ((8,), jnp.int32))


@pytest.mark.parametrize("d,f", [(D, FF), (FF, D)])
def test_householder_gemm_and_bwd_compile(one_chip, d, f):
    from repro.kernels import ops
    t = 8 * 512                                     # batch 8, seq 512
    u = ((N_BLOCKS, d // N_BLOCKS), F32)
    _compile(lambda x, w, u: ops.householder_gemm(x, w, u, interpret=False),
             one_chip, ((t, d), BF16), ((d, f), BF16), u)
    _compile(lambda x, w, u, g: ops.householder_gemm_bwd(
        x, w, u, g, interpret=False), one_chip,
        ((t, d), BF16), ((d, f), BF16), u, ((t, f), BF16))


@pytest.mark.parametrize("d,f", [(D, FF), (FF, D)])
def test_ether_merge_compiles(one_chip, d, f):
    from repro.kernels import ops
    _compile(lambda w, u: ops.ether_merge(w, u, interpret=False), one_chip,
             ((d, f), BF16), ((N_BLOCKS, d // N_BLOCKS), F32))


def test_compile_cache_helper_respects_env(monkeypatch, tmp_path):
    from repro.common import compile_cache
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    calls: list = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert calls == []                  # the environment's choice stands
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable()
    assert path == compile_cache.DEFAULT_DIR
    assert os.path.isabs(path) and path.endswith(".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
