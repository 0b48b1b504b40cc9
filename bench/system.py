"""The bridge from a configuration file to the system under test.

This is the one module of the benchmark that turns a file's keys into
the program's own objects (``ModelConfig``, ``PEFTConfig``).  It refuses
a file that states something the program cannot run, so a configuration
is never reported under a block it did not get.
"""

from __future__ import annotations

import jax

from bench import model

# what the program's dense block hard-codes (models/layers.py, backbone.py)
PROGRAM_NORM_EPS = 1e-6


def model_config(cfg: dict):
    from repro.models import ModelConfig
    m = model.dims(cfg)
    if m["eps"] != PROGRAM_NORM_EPS:
        raise ValueError(f"{cfg['name']}: norm eps {m['eps']} != the "
                         f"program's {PROGRAM_NORM_EPS}")
    if cfg.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError(f"{cfg['name']}: the program rotates whole heads")
    prog = cfg["program"]
    return ModelConfig(
        name=cfg["name"], n_layers=m["L"], d_model=m["d"], n_heads=m["H"],
        n_kv=m["Hkv"], d_ff=m["ff"], vocab=m["V"],
        mlp_type="swiglu" if m["glu"] else "gelu",
        act="silu" if m["glu"] else "gelu", rope_theta=m["theta"],
        tie_embeddings=m["tied"], param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["torch_dtype"], remat=prog["remat"],
        q_chunk=prog["q_chunk"], loss_chunk=prog["loss_chunk"])


def peft_config(cfg: dict):
    from repro.core.transforms import PEFTConfig
    a = cfg["adapter"]
    return PEFTConfig(method=a["method"], n_blocks=a["n_blocks"],
                      targets="|".join(model.targets(cfg)),
                      backend=a["backend"], adapter_dtype=a["dtype"])


def check_layout(cfg: dict, mcfg, peft, weights, adapters) -> None:
    """The benchmark's weights and adapters must be exactly the trees the
    program builds for this configuration (shapes and dtypes)."""
    from repro.core.peft import init_adapters
    from repro.models import init_model
    want_w = jax.eval_shape(lambda k: init_model(k, mcfg),
                            jax.random.PRNGKey(0))
    want_a = jax.eval_shape(
        lambda k: init_adapters(k, init_model(k, mcfg), peft),
        jax.random.PRNGKey(0))

    def sig(tree):
        return jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)),
                                      tree)

    for what, got, want in (("weights", weights, want_w),
                            ("adapters", adapters, want_a)):
        if sig(got) != sig(want):
            raise ValueError(f"{cfg['name']}: benchmark {what} do not match "
                             f"the program's tree")
