"""The needed-work counts against hand numbers: the benchmark's Phi-1.5
configuration, and MiniCPM-2B's published widths (SwiGLU, 36 heads,
d 2304, vocabulary 122753) as a second shape the counts must hold for."""

import json
import os

import pytest

from bench import counts, model

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def phi():
    return _cfg("phi15-ether32")


@pytest.fixture(scope="module")
def cpm():
    # openbmb/MiniCPM-2B's widths; only the keys the counts read
    return {"name": "minicpm2b", "vocab_size": 122753, "hidden_size": 2304,
            "intermediate_size": 5760, "num_hidden_layers": 40,
            "num_attention_heads": 36, "num_key_value_heads": 36,
            "hidden_act": "silu", "rope_theta": 10000.0,
            "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
            "adapter": {"n_blocks": 32, "targets": [
                "q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                "up_proj", "down_proj"]}}


def test_weight_bytes(phi, cpm):
    # Phi-1.5: 24 x (4 * 2048^2 + 2 * 2048 * 8192 + 2 * 2048) + the
    # 51200 x 2048 output head's matrix + the final norm, 2 bytes each
    layer = 4 * 2048 ** 2 + 2 * 2048 * 8192 + 2 * 2048
    assert counts.weight_bytes(phi) == 2 * (24 * layer + 51200 * 2048 + 2048)
    assert counts.weight_bytes(phi) == 2625835008
    # MiniCPM-2B: SwiGLU has three 2304 x 5760 matrices
    layer = 4 * 2304 ** 2 + 3 * 2304 * 5760 + 2 * 2304
    assert counts.weight_bytes(cpm) == 2 * (40 * layer + 122753 * 2304
                                            + 2304)
    # tied or not, the output head's matrix is read once
    assert counts.weight_bytes(dict(phi, tie_word_embeddings=False)) == \
        counts.weight_bytes(phi)


def test_decode_step_phi(phi):
    # 16 sequences with 600 live positions each, 12 distinct tenants
    flops, nbytes = counts.decode_step(phi, [600] * 16, 12)
    n_layer = 4 * 2048 ** 2 + 2 * 2048 * 8192
    aw = 5 * 2048 + 8192                   # q, k, v, o, up inputs; down
    want_f = (16 * (2 * 24 * n_layer + 2 * 2048 * 51200)
              + 4 * 24 * 32 * 64 * 600 * 16 + 16 * 24 * 4 * aw)
    kv = 24 * 2 * 32 * 64 * 2 * (600 * 16 + 16)
    want_b = 2625835008 + kv + 12 * 24 * aw * 4
    assert (flops, nbytes) == (want_f, want_b)
    # the merged tier gathers no bank rows and reflects nothing
    f2, b2 = counts.decode_step(phi, [600] * 16, 12, bank=False)
    assert want_f - f2 == 16 * 24 * 4 * aw and want_b - b2 == 12 * 24 * aw * 4


def test_decode_step_minicpm_is_byte_bound(cpm):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    work = counts.decode_step(cpm, [700] * 8, 8)
    t, bound = counts.seconds(work, peak)
    assert bound == "bytes"
    # 5.45 GB of weights, 8 x 700 live positions (and 8 written) x 40
    # layers x k and v x 36 heads x 64 x 2 B, 8 bank rows of float32
    kv = (8 * 700 + 8) * 40 * 2 * 36 * 64 * 2
    rows = 8 * 40 * (6 * 2304 + 5760) * 4
    assert work[1] == 5449761792 + kv + rows
    assert t == pytest.approx(work[1] / 819e9)


def test_bank_gemms_phi(phi):
    f, b = counts.decode_bank_gemms(phi, 16, 10)
    shapes = [(2048, 2048)] * 4 + [(2048, 8192), (8192, 2048)]
    want_f = 24 * sum(2 * 16 * d * o + 4 * 16 * d for d, o in shapes)
    want_b = 24 * sum(2 * (d * o + 16 * d + 16 * o) + 4 * 10 * d
                      for d, o in shapes)
    assert (f, b) == (want_f, want_b)


def test_prefill_and_train(phi):
    f, b = counts.prefill(phi, 512)
    n_layer = 4 * 2048 ** 2 + 2 * 2048 * 8192
    assert f == (2 * 24 * n_layer * 512 + 2 * 2048 * 51200
                 + 2 * 24 * 32 * 64 * 512 ** 2 + 4 * 24 * (5 * 2048 + 8192)
                 * 512)
    tf, tb = counts.train_step(phi, 16, 512)
    n = 24 * n_layer + 2048 * 51200
    attn = 2 * 24 * 32 * 64 * 512 * 512 * 16
    assert tf == 4 * n * 8192 + 3 * attn + 12 * 24 * (5 * 2048 + 8192) * 8192
    assert tb == 2 * counts.weight_bytes(phi)
    # about 46 TFLOP per step at batch 16 x 512
    assert 40e12 < tf < 52e12


def test_kernel_shapes_and_blocks(phi, cpm):
    assert model.kernel_shapes(cpm)["down_proj"] == (5760, 2304)
    assert model.targets(cpm) == ("q_proj", "k_proj", "v_proj", "o_proj",
                                  "gate_proj", "up_proj", "down_proj")
    assert model.n_blocks(cpm, 5760) == 32 and 5760 // 32 == 180
    assert model.n_blocks(phi, 8192) == 32
