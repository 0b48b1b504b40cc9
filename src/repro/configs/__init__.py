"""Architecture registry: one module per assigned architecture (exact
configs from the assignment sheet) plus the paper's own models.

Each module exports:
    ARCH            — metadata dict (family, source, notes)
    full()          — the exact published config (runs on a chip where
                      it fits: phi-1.5 on one v5e; chip_smoke.py)
    smoke()         — reduced same-family config (CPU tests)
    PEFT_TARGETS    — default ETHER target regex for this family
"""

from __future__ import annotations

import importlib

ARCH_IDS = [
    "llava_next_mistral_7b",
    "qwen3_moe_235b_a22b",
    "olmoe_1b_7b",
    "mamba2_1p3b",
    "smollm_360m",
    "deepseek_coder_33b",
    "minicpm_2b",
    "qwen2p5_32b",
    "recurrentgemma_9b",
    "whisper_large_v3",
    # paper's own models (benchmarks)
    "paper_llama2_7b",
    "paper_phi1p5",
]

# CLI-friendly aliases (assignment sheet ids → module names)
ALIASES = {
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "mamba2-1.3b": "mamba2_1p3b",
    "smollm-360m": "smollm_360m",
    "deepseek-coder-33b": "deepseek_coder_33b",
    "minicpm-2b": "minicpm_2b",
    "qwen2.5-32b": "qwen2p5_32b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "whisper-large-v3": "whisper_large_v3",
    "llama-2-7b": "paper_llama2_7b",
    "phi-1.5": "paper_phi1p5",
}

ASSIGNED = [a for a in ALIASES if not a.startswith(("llama", "phi"))]


def get_module(arch: str):
    mod = ALIASES.get(arch, arch).replace("-", "_").replace(".", "p")
    return importlib.import_module(f"repro.configs.{mod}")


def get_config(arch: str, variant: str = "full"):
    m = get_module(arch)
    return m.full() if variant == "full" else m.smoke()


def peft_targets(arch: str) -> str:
    return get_module(arch).PEFT_TARGETS
