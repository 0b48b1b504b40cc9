"""Pad-invariant recurrent prefill (DESIGN.md §10).

The serve engine right-pads every prompt to a fixed bucket; recurrent
state must nonetheless come out equal to the unpadded prompt's state.
The mask algebra makes pad positions identity state updates:

* SSD:    log-decay ``a → 0`` (decay 1 passes state through) and
          ``xv → 0`` (no injection) — the same mechanism ``ssd_chunked``
          uses internally for chunk-multiple padding;
* RG-LRU: ``log a_t → 0`` (a_t = 1) and gated input ``→ 0``, plus a
          gather at ``true_lens - 1`` (associative_scan regroups its
          combine tree under longer sequences, so reading the
          propagated last position is last-ulp-unstable — the interior
          prefix is not);
* conv:   the streamed W-1 tail is gathered at the last *real* inputs.

These are property tests: pad positions carry garbage (b/c) or zeros,
lengths cover shorter-than-conv-tail prompts, non-chunk-multiples and
chunk-multiples, and the block-level checks run in bf16 params too.
Final recurrent states are accumulated in f32 regardless of param dtype
and must match the unpadded oracle to within ``PAD_ULP_BOUND`` units of
eps·max|state| (DESIGN.md §10: the padded program's reductions are
grouped by its own shape); the scan prefixes and the conv tail gather,
fed identical inputs, stay bitwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.rglru import init_rglru_block, rglru_block, rglru_scan
from repro.models.ssm import (_causal_conv, init_mamba2, mamba2_block,
                              ssd_chunked, ssm_dims)

RNG = np.random.default_rng(0)

# DESIGN.md §10 "Bound": padded vs unpadded recurrent state, in units of
# eps(dtype)·max|state| of the leaf.
PAD_ULP_BOUND = 8


def assert_state_close(want, got, err_msg=""):
    """Recurrent-state agreement within the §10 ulp bound."""
    want, got = np.asarray(want), np.asarray(got)
    assert want.shape == got.shape and want.dtype == got.dtype, err_msg
    w, g = want.astype(np.float64), got.astype(np.float64)
    tol = PAD_ULP_BOUND * float(jnp.finfo(want.dtype).eps) * np.abs(w).max()
    err = np.abs(w - g).max()
    assert err <= tol, f"{err_msg} |Δ|={err:.3g} > {tol:.3g}"


def _pad(arr, pad_len, fill="zero"):
    """Right-pad axis 1 with zeros or garbage (proves invariance does
    not depend on pad *values* where the algebra kills them)."""
    B = arr.shape[0]
    tail_shape = (B, pad_len) + arr.shape[2:]
    tail = (np.zeros(tail_shape, arr.dtype) if fill == "zero" else
            RNG.standard_normal(tail_shape).astype(arr.dtype))
    return np.concatenate([arr, tail], axis=1)


# ---------------------------------------------------------------------------
# ssd_chunked
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s0,s_pad,chunk", [
    (5, 16, 8),     # non-chunk-multiple true length
    (2, 16, 8),     # shorter than conv_width-1 territory
    (1, 16, 4),     # single real token
    (8, 16, 8),     # exact chunk multiple
    (13, 32, 8),    # pads spanning extra whole chunks
    (7, 16, 16),    # true length < one chunk
])
def test_ssd_chunked_pad_invariant_state_bitwise(s0, s_pad, chunk):
    B, H, P, G, N = 2, 4, 8, 2, 16
    xv = RNG.standard_normal((B, s0, H, P)).astype(np.float32)
    a = -np.abs(RNG.standard_normal((B, s0, H))).astype(np.float32)
    b = RNG.standard_normal((B, s0, G, N)).astype(np.float32)
    c = RNG.standard_normal((B, s0, G, N)).astype(np.float32)
    init = RNG.standard_normal((B, H, N, P)).astype(np.float32)

    y0, f0 = ssd_chunked(jnp.asarray(xv), jnp.asarray(a), jnp.asarray(b),
                         jnp.asarray(c), chunk=chunk,
                         initial_state=jnp.asarray(init))
    pad = s_pad - s0
    # the mask algebra: a=0, xv=0 at pads; b/c deliberately GARBAGE
    y1, f1 = ssd_chunked(
        jnp.asarray(_pad(xv, pad)), jnp.asarray(_pad(a, pad)),
        jnp.asarray(_pad(b, pad, "garbage")),
        jnp.asarray(_pad(c, pad, "garbage")), chunk=chunk,
        initial_state=jnp.asarray(init))
    assert_state_close(f0, f1)
    # outputs at real positions are unaffected by pads (causality);
    # allclose not bitwise: a different chunk layout (s0 < chunk) may
    # regroup the intra-chunk reduction
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1)[:, :s0],
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s0,s_pad", [(5, 16), (2, 16), (1, 8), (13, 32),
                                      (16, 16)])
def test_rglru_scan_pad_identity_prefixes_bitwise(s0, s_pad):
    """Identity pads (a=1, b=0) leave every real-position prefix of the
    associative scan bitwise-unchanged — the property the block's
    ``true_lens - 1`` state gather relies on."""
    B, D = 2, 32
    u = RNG.standard_normal((B, s0, D)).astype(np.float32)
    al = (-np.abs(RNG.standard_normal((B, s0, D))) * 0.1).astype(np.float32)
    h0 = RNG.standard_normal((B, D)).astype(np.float32)
    hs0, f0 = rglru_scan(jnp.asarray(u), jnp.asarray(al), jnp.asarray(h0))
    pad = s_pad - s0
    hs1, _ = rglru_scan(jnp.asarray(_pad(u, pad)),
                        jnp.asarray(_pad(al, pad)), jnp.asarray(h0))
    np.testing.assert_array_equal(np.asarray(hs0),
                                  np.asarray(hs1)[:, :s0])
    np.testing.assert_array_equal(np.asarray(f0),
                                  np.asarray(hs1)[:, s0 - 1])


# ---------------------------------------------------------------------------
# depthwise-conv streamed tail
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s0", [1, 2, 3, 5, 11])
def test_causal_conv_tail_holds_last_real_inputs(s0):
    """The streamed W-1 context window must hold the last real inputs,
    not pad garbage — including prompts shorter than W-1, where the
    tail picks up the same leading zero-state an unpadded prompt has."""
    B, C, W, S = 2, 6, 4, 16
    x = RNG.standard_normal((B, s0, C)).astype(np.float32)
    kern = RNG.standard_normal((W, C)).astype(np.float32)
    bias = RNG.standard_normal((C,)).astype(np.float32)
    y0, st0 = _causal_conv(jnp.asarray(x), jnp.asarray(kern),
                           jnp.asarray(bias))
    xp = _pad(x, S - s0, "garbage")
    y1, st1 = _causal_conv(jnp.asarray(xp), jnp.asarray(kern),
                           jnp.asarray(bias),
                           true_lens=jnp.full((B,), s0, jnp.int32))
    np.testing.assert_array_equal(np.asarray(st0), np.asarray(st1))
    np.testing.assert_array_equal(np.asarray(y0),
                                  np.asarray(y1)[:, :s0])


# ---------------------------------------------------------------------------
# full blocks, f32 and bf16 params
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s0,s_pad", [(2, 16), (5, 16), (9, 16), (16, 16)])
def test_mamba2_block_true_lens_state_bitwise(dtype, s0, s_pad):
    d_model, B = 32, 2
    kw = dict(expand=2, headdim=8, d_state=8, n_groups=1)
    p = init_mamba2(jax.random.PRNGKey(1), d_model, jnp.dtype(dtype), **kw)
    x = RNG.standard_normal((B, s0, d_model)).astype(dtype)
    xp = _pad(x, s_pad - s0, "garbage")
    _, c0 = mamba2_block(p, jnp.asarray(x), d_model=d_model, chunk=4, **kw)
    _, c1 = mamba2_block(p, jnp.asarray(xp), d_model=d_model, chunk=4,
                         true_lens=jnp.full((B,), s0, jnp.int32), **kw)
    assert c1["ssm"].dtype == jnp.float32
    assert_state_close(c0["ssm"], c1["ssm"])
    assert_state_close(c0["conv"], c1["conv"])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s0,s_pad", [(2, 16), (5, 16), (13, 32), (16, 16)])
def test_rglru_block_true_lens_state_bitwise(dtype, s0, s_pad):
    d_model, d_rnn, heads, B = 32, 32, 4, 2
    p = init_rglru_block(jax.random.PRNGKey(2), d_model, d_rnn, heads,
                         jnp.dtype(dtype))
    x = RNG.standard_normal((B, s0, d_model)).astype(dtype)
    xp = _pad(x, s_pad - s0, "garbage")
    _, c0 = rglru_block(p, jnp.asarray(x), d_rnn=d_rnn, n_heads=heads)
    _, c1 = rglru_block(p, jnp.asarray(xp), d_rnn=d_rnn, n_heads=heads,
                        true_lens=jnp.full((B,), s0, jnp.int32))
    assert c1["h"].dtype == jnp.float32
    assert_state_close(c0["h"], c1["h"])
    assert_state_close(c0["conv"], c1["conv"])


def test_blocks_ragged_true_lens_rows_independent():
    """Different true lengths per batch row: each row's state equals a
    B=1 unpadded run of that row — rows never contaminate each other."""
    d_model, B, S = 32, 3, 16
    lens = [2, 7, 16]
    kw = dict(expand=2, headdim=8, d_state=8, n_groups=1)
    p = init_mamba2(jax.random.PRNGKey(3), d_model, jnp.float32, **kw)
    x = RNG.standard_normal((B, S, d_model)).astype(np.float32)
    _, batched = mamba2_block(p, jnp.asarray(x), d_model=d_model, chunk=4,
                              true_lens=jnp.asarray(lens, jnp.int32), **kw)
    for row, s0 in enumerate(lens):
        _, solo = mamba2_block(p, jnp.asarray(x[row:row + 1, :s0]),
                               d_model=d_model, chunk=4, **kw)
        assert_state_close(solo["ssm"][0], batched["ssm"][row])
        assert_state_close(solo["conv"][0], batched["conv"][row])


def test_backbone_prefill_true_lens_matches_unpadded_cache():
    """End-to-end through api.prefill: every recurrent cache leaf of a
    padded true_lens prefill equals the unpadded prompt's, and the
    gathered logits match the unpadded last-position logits."""
    from repro.configs import get_config
    from repro.models import api, init_model
    for arch, s0, s_pad in [("mamba2-1.3b", 5, 16),
                            ("recurrentgemma-9b", 5, 16)]:
        cfg = get_config(arch, "smoke")
        cfg = dataclasses.replace(cfg, window=s_pad) \
            if getattr(cfg, "window", None) else cfg
        params = init_model(jax.random.PRNGKey(4), cfg)
        toks = RNG.integers(0, cfg.vocab, (1, s0)).astype(np.int32)
        padded = np.zeros((1, s_pad), np.int32)
        padded[:, :s0] = toks
        cache0, logits0 = api.prefill(params, None, {"tokens": toks},
                                      cfg, None)
        cache1, logits1 = api.prefill(
            params, None, {"tokens": padded}, cfg, None,
            true_lens=np.asarray([s0], np.int32))
        np.testing.assert_allclose(np.asarray(logits0[:, -1]),
                                   np.asarray(logits1[:, -1]),
                                   rtol=2e-6, atol=2e-6)
        flat0 = jax.tree_util.tree_leaves_with_path(cache0)
        flat1 = dict(jax.tree_util.tree_leaves_with_path(cache1))
        for path, leaf in flat0:
            name = jax.tree_util.keystr(path)
            if any(k in name for k in ("ssm", "conv", "'h'")):
                assert_state_close(leaf, flat1[path], f"{arch}:{name}")
