"""The bridge from a configuration file to the system under test, for
what every architecture shares: the adapter's ``PEFTConfig``, and the
check that the benchmark's trees are the ones the program builds.

The architecture's half, the ``ModelConfig`` of its block with the
refusals of what the program cannot run, is its module's
``model_config`` (``bench/archs/<arch>.py``), so a configuration is
never reported under a block it did not get.
"""

from __future__ import annotations

import jax

from bench import harness


def peft_config(cfg: dict):
    from repro.core.transforms import PEFTConfig
    a = cfg["adapter"]
    have = {p.rsplit("/", 1)[-1]
            for u in harness.arch(cfg).projections(cfg) for p in u.shapes}
    return PEFTConfig(method=a["method"], n_blocks=a["n_blocks"],
                      targets="|".join(t for t in a["targets"] if t in have),
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
