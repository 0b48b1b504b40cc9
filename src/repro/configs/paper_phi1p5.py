"""Phi-1.5 (1.3B) — the paper's Table 1 FLOPs-comparison model:
24L d_model=2048 32H d_ff=8192 vocab=51200 (internal dim 2048), a plain
two-matrix GELU MLP (fc1/fc2 → up_proj/down_proj), ~1.4B parameters.
"""

from repro.configs._common import DENSE_TARGETS, FULL, SMOKE
from repro.models import ModelConfig

ARCH = {"id": "phi-1.5", "family": "dense",
        "long_500k": False, "decode": True}
PEFT_TARGETS = DENSE_TARGETS


def full() -> ModelConfig:
    return ModelConfig(
        name="phi-1.5", n_layers=24, d_model=2048, n_heads=32, n_kv=32,
        d_ff=8192, vocab=51200, act="gelu", mlp_type="gelu", **FULL)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="phi-smoke", n_layers=3, d_model=64, n_heads=4, n_kv=4,
        d_ff=256, vocab=512, act="gelu", mlp_type="gelu", **SMOKE)
