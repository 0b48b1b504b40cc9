"""What the cells read, pinned: the needed-work counts of
``phi15-ether32``, the weights and adapters of ``tiny.json``, the
reference programs, and the readers on the recorded chip trace, each
equal to the value read before the block moved into its architecture
module (``bench/archs/``).  A change here changes what the benchmark
measures."""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, harness, model
from bench import trace as tr
from bench.drivers import finetune, serve

BENCH = harness.BENCH
DATA = os.path.join(BENCH, "tests", "data")


def _load(*parts):
    return harness.load_json(os.path.join(*parts))


@pytest.fixture(scope="module")
def phi():
    return _load(BENCH, "configs", "phi15-ether32.json")


@pytest.fixture(scope="module")
def tiny():
    return _load(DATA, "tiny.json")


MIXED = [1020, 37, 1500, 5, 812, 1100, 129, 64]
COUNTS = [
    ("weight_bytes", (), 2625835008),
    ("decode_step", ([600] * 16, 5, True), (43925897216, 4525264896)),
    ("decode_step", ([600] * 16, 5, False), (43897585664, 4516417536)),
    ("decode_step", (MIXED, 5, True), (21936799744, 3553824768)),
    ("decode_step", (MIXED, 5, False), (21922643968, 3544977408)),
    ("prefill", (512, True), (1263836069888, 2728267776)),
    ("prefill", (512, False), (1262930100224, 2726498304)),
    ("prefill", (1333, True), (3397663883264, 2889682944)),
    ("prefill", (1333, False), (3395305177088, 2887913472)),
    ("train_step", (16, 512), (44298829561856, 5251670016)),
]


@pytest.mark.parametrize("fn,args,want", COUNTS,
                         ids=[f"{f}{i}" for i, (f, *_) in enumerate(COUNTS)])
def test_phi_counts(phi, fn, args, want):
    assert getattr(harness.arch(phi), fn)(phi, *args) == want


@pytest.mark.parametrize("active,tenants,want", [
    (8, 6, (19341508608, 2440691712)),
    (16, 10, (38683017216, 2461925376))])
def test_phi_bank_gemms(phi, active, tenants, want):
    assert counts.decode_bank_gemms(phi, active, tenants) == want


def _tree_hash(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{x.dtype}{x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,want", [
    (1, "42c6108d16e86a673f118007aa7fd53ff2e8890564eda897ca3a1715f7771f02"),
    (3000001501,
     "10809cb1911f0be5bfd6a82a9361a30139d43faba9a78065a1d1a5f6e7cc7621")])
def test_tiny_weights(tiny, seed, want):
    assert _tree_hash(harness.arch(tiny).make_weights(tiny, seed)) == want


@pytest.mark.parametrize("seed,tid,want", [
    (1, 0, "a2b0ce65927a73919b95c9aafa48fcfbf5d5b7e40e6cef952c07e19aaf41809e"),
    (1, 5, "cd51e7f91f84daf98babcd5e0e4fca031c2fc770f65f9c50a0b416048127470d"),
    (3000001501, 0,
     "a803999b4a7271693c8bc9593aac743dca11f1621c4a10aeb57da5921264e4c2"),
    (3000001501, 5,
     "ece97ba50a1588c089d92e2ad50750c973fce23a6a2c365fb1a7800a057f2c2a")])
def test_tiny_adapters(tiny, seed, tid, want):
    assert _tree_hash(model.adapter_fn(tiny, seed)(tid)) == want


OPT = {"lr": 2e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "clip": 1.0}
REFERENCE = [
    ("served", None,
     "377eff92d91cca94f8988fb410e37a9c015eb4ffbe59b6bc55e2a7275fafbc20"),
    ("served", "bfloat16",
     "7e1f878d288d15162be3c55b9c1ebb010bb371e9378d8c5aefa53383fbc4c132"),
    ("train", (None, None),
     "d6611a84ae3033e554e01fe07d7afe5b1f967bfef8829afbb18834a7485cc0b4"),
    ("train", ("bfloat16", None),
     "2a357f64bcdaa5a3549947e1070cf0daf42eff3e4a7bb5bc87bea53ddd43c3b1"),
    ("train", (None, 2),
     "77174c1f908a4de3f609cbfe426312a9c5761acb7e8457f0436f49c8e0456f21"),
]


@pytest.mark.parametrize("kind,args,want", REFERENCE,
                         ids=["served", "served_control", "train",
                              "train_control", "train_half_batch"])
def test_tiny_reference_programs(tiny, kind, args, want):
    """The reference's programs, lowered without source locations."""
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    w = jax.eval_shape(lambda: harness.arch(tiny).make_weights(tiny, 1))
    ad = jax.eval_shape(lambda: model.adapter_fn(tiny, 1)(0))
    if kind == "served":
        fn = model.served_gaps(tiny, args)
        low = fn.lower(w, ad, sds((tiny["serving"]["max_len"],), i32),
                       sds((24,), i32), sds((24,), i32))
    else:
        mu = jax.tree_util.tree_map(lambda x: sds(x.shape, jnp.float32), ad)
        fn = model.train_steps(tiny, OPT, *args)
        low = fn.lower(w, ad, mu, mu, sds((), i32), sds((4, 16), i32),
                       sds((4, 16), i32))
    text = low.as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == want


@pytest.fixture(scope="module")
def recorded():
    """The readers' inputs at ``record_scopes.py``'s own sizes: its
    configuration, its trace, three bank decode steps (n 0-2, one
    sequence of one tenant) and two finetune steps (n 3-4, 2 x 64)."""
    cfg = _load(DATA, "scopes.json")
    trace = tr.load(os.path.join(DATA, "tpu-scopes.xplane.pb"))
    peak = harness.peaks("TPU v5 lite")
    steps = [serve.StepRec(n, 0.0, 0.01, (41 + n,), 1, "bank", True)
             for n in range(3)]
    lay = serve.Layer(cfg=cfg, peak=peak, t0=0.0, t1=1.0, requests=[],
                      steps=steps, tier_tokens={}, ttft_ms=[], tpot_ms=[])
    mix = dict(_load(BENCH, "traffic", "finetune-b16s512.json"),
               batch=2, seq=64)
    fsteps = [finetune.StepRec(n, 0.0, 0.05, 7.0, True) for n in range(3, 5)]
    return {"serve": harness.Outcome(
                e2e={}, layer=lay, checks={}, attempted=0, failed=0,
                trace=trace),
            "finetune": harness.Outcome(
                e2e={}, layer=finetune.Layer(cfg, mix, peak, fsteps),
                checks={}, attempted=0, failed=0, trace=trace)}


@pytest.mark.parametrize("metric,driver,want", [
    ("roofline.bank_gemm", "serve", 2.7123885886100867),
    ("mfu.decode", "serve", 2.771954876340499),
    ("roofline.adapter_gemm.train", "finetune", 10.950375677497926),
    ("mfu.train", "finetune", 0.01973132605076142)])
def test_readers_on_recorded_trace(recorded, metric, driver, want):
    assert harness.reader(metric).read(recorded[driver]) == want
