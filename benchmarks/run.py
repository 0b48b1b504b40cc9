# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure plus the
roofline report and the tracked kernel/train/serve suites.

    python -m benchmarks.run [--only substr]          # paper tables
    python -m benchmarks.run --suite kernels \
        --json BENCH_kernels.json                     # kernel suite
    python -m benchmarks.run --suite train \
        --json BENCH_train.json                       # training suite
    python -m benchmarks.run --suite serve \
        --json BENCH_serve.json                       # serving suite
    python -m benchmarks.run --suite kernels --shapes tiny \
        --compare BENCH_kernels.json                  # regression gate

The kernel suite times every forward (op, backend) pair registered in
``core.execute`` at serving shapes; the train suite times value-and-grad
plus the ``*_bwd`` backward dispatches and a real trainer step; the
serve suite replays the continuous-batching engine (throughput, latency
tails, tenant churn).  All fail if a registered pair/row is missing an
entry; ``--json`` writes the tracked payload (regenerate at the repo
root with exactly the commands above).  ``--include-interp`` opts into
timing Pallas interpret-mode rows off-TPU (they measure the Python
emulator, not the kernel).

Every suite emits rows in one shared schema — (op, backend, kind, what,
shape) keyed by ``benchmarks._common.entry_key`` — so ``--compare``
gates all of them through the same code path.

``--compare OLD.json`` re-runs the suite recorded in OLD at the same
shape grid and exits nonzero if any jnp row got more than ``--threshold``
(default 1.3×) slower — jnp rows only, because pallas rows off-TPU time
the emulator.  Slowdowns are normalized by the median ratio (a uniformly
slower/faster machine doesn't flag anything); a median above 3× fails
outright, since that is either a shared-hot-path regression hitting
every row or a baseline from a different machine class.  Rows faster
than ``--noise-floor-us`` in the baseline are additionally judged on
absolute slowdown (µs-scale timings jitter far more than 30%), so
tiny-shape CI runs don't flake on scheduler noise.  Known blind spot:
a uniform sub-3× slowdown of every row on same-class hardware is
absorbed by the normalization.
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
import traceback

MODULES = [
    "benchmarks.table1_flops",        # paper Table 1
    "benchmarks.table23_params",      # Tables 2/3 (+4/5 #params)
    "benchmarks.table45_convergence", # Tables 4/5 proxy
    "benchmarks.fig4_distances",      # Fig. 4
    "benchmarks.fig56_lr_robustness", # Figs. 5/6
    "benchmarks.table6_he_study",     # Table 6 / Fig. 7
    "benchmarks.ablation_blocks",     # App. D.1
    "benchmarks.ablation_sides",      # App. D.2
    "benchmarks.kernels_micro",       # kernel timings
    "benchmarks.roofline",            # §Roofline from dry-run JSONs
]


# Tracked suites: one module per suite, every module exposing
# ``run_suite(shapes, include_interp)`` returning rows in the shared
# entry_key schema (so the --compare gate below is suite-agnostic).
SUITES = {
    "kernels": "benchmarks.kernels_suite",
    "train": "benchmarks.train_suite",
    "serve": "benchmarks.serve_suite",
}


def _suite_payload(suite: str, shapes: str, include_interp: bool) -> dict:
    import importlib
    mod = importlib.import_module(SUITES[suite])
    return mod.run_suite(shapes=shapes, include_interp=include_interp)


_MAX_MACHINE_FACTOR = 3.0


def _compare(old_path: str, fresh: dict, threshold: float,
             noise_floor_us: float) -> int:
    """Diff fresh jnp rows against a committed baseline payload.

    Slowdowns are judged MACHINE-NORMALIZED: each row's new/old ratio is
    divided by the median ratio across all compared rows, so a runner
    that is uniformly 1.5× slower (or faster) than the baseline box does
    not flag (or mask) anything — only rows that regressed *relative to
    the rest of the suite* by more than ``threshold`` fail.  Rows whose
    baseline is under the noise floor must also regress by the floor in
    absolute µs.  Returns the number of failures; baseline rows with no
    fresh counterpart (shape-grid drift) and empty comparisons count as
    failures too — a gate that compares nothing must not pass."""
    from benchmarks._common import entry_key
    with open(old_path) as f:
        old = json.load(f)
    if old.get("suite") != fresh.get("suite"):
        print(f"# --compare: baseline suite {old.get('suite')!r} != "
              f"fresh {fresh.get('suite')!r}", file=sys.stderr)
        return 1
    old_rows = {entry_key(e): e for e in old["entries"]
                if e["backend"] == "jnp"}
    pairs = []
    for e in fresh["entries"]:
        if e["backend"] != "jnp":
            continue
        base = old_rows.pop(entry_key(e), None)
        if base is None:
            print(f"#   NEW   {e['op']}/{e['kind']} {e['shape']}",
                  file=sys.stderr)
            continue
        pairs.append((e, base,
                      e["us_per_call"] / max(base["us_per_call"], 1e-9)))
    print("# compare vs", old_path, f"(threshold {threshold}x "
          f"machine-normalized, noise floor {noise_floor_us}us)",
          file=sys.stderr)
    if not pairs:
        print("# --compare matched ZERO rows — baseline and fresh grids "
              "disagree; regenerate the baseline", file=sys.stderr)
        return 1
    ratios = sorted(r for _, _, r in pairs)
    speed = ratios[len(ratios) // 2]          # median machine factor
    print(f"#   median machine factor {speed:.2f}x", file=sys.stderr)
    if speed > _MAX_MACHINE_FACTOR:
        # Normalization's blind spot: a regression in shared hot-path
        # code slows EVERY row and looks like a slow machine.  A
        # same-class CI runner should never be this far off the
        # baseline box, so a huge median is either that blind spot or
        # a baseline that needs regenerating — fail either way.
        print(f"# median {speed:.2f}x exceeds {_MAX_MACHINE_FACTOR}x: "
              f"suite-wide slowdown (shared-code regression, or the "
              f"baseline was recorded on a much faster machine — "
              f"regenerate it)", file=sys.stderr)
        return len(pairs)
    regressions = []
    for e, base, ratio in pairs:
        rel = ratio / speed
        slow = rel > threshold and (
            base["us_per_call"] >= noise_floor_us
            or e["us_per_call"] - base["us_per_call"] >= noise_floor_us)
        tag = "SLOWER" if slow else ("faster" if rel < 1 / threshold
                                     else "ok")
        print(f"#   {tag:6s} {e['op']}/{e['kind']} d={e['shape']['d']}: "
              f"{base['us_per_call']:.1f} -> {e['us_per_call']:.1f}us "
              f"({ratio:.2f}x raw, {rel:.2f}x normalized)",
              file=sys.stderr)
        if slow:
            regressions.append(e)
    gone = len(old_rows)
    for k in old_rows:
        print(f"#   GONE  {k[0]}/{k[2]} — baseline row has no fresh "
              f"counterpart", file=sys.stderr)
    if regressions or gone:
        print(f"# {len(regressions)} jnp row(s) regressed beyond "
              f"{threshold}x normalized; {gone} baseline row(s) vanished",
              file=sys.stderr)
    return len(regressions) + gone


def _run_suite(args) -> None:
    payload = _suite_payload(args.suite, args.shapes, args.include_interp)
    print("name,us_per_call,derived")
    for e in payload["entries"]:
        s = e["shape"]
        what = e.get("what", "fwd")
        print(f"{payload['suite']}/{e['op']}/{e['backend']}/{e['kind']}"
              f"_b{s['batch']}x{s['tokens']}_d{s['d']},"
              f"{e['us_per_call']:.1f},{e['mode']};{what}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(f"# wrote {args.json} ({len(payload['entries'])} entries)",
              file=sys.stderr)
    if args.compare:
        if _compare(args.compare, payload, args.threshold,
                    args.noise_floor_us):
            sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--suite", default=None, choices=tuple(SUITES),
                    help="run a tracked suite instead of the paper tables")
    ap.add_argument("--json", default=None,
                    help="write the suite payload to this JSON file")
    ap.add_argument("--shapes", default="serving",
                    choices=("serving", "tiny"),
                    help="suite shape grid (tiny = CI smoke)")
    ap.add_argument("--include-interp", action="store_true",
                    help="time Pallas interpret-mode rows off-TPU "
                         "(measures the emulator; off by default)")
    ap.add_argument("--compare", default=None, metavar="OLD.json",
                    help="regression mode: diff this fresh suite run "
                         "against a committed baseline payload and exit "
                         "nonzero on jnp-row slowdowns")
    ap.add_argument("--threshold", type=float, default=1.3,
                    help="slowdown ratio that fails --compare (1.3x)")
    ap.add_argument("--noise-floor-us", type=float, default=200.0,
                    help="baseline rows faster than this are judged on "
                         "absolute slowdown too (timer noise)")
    args = ap.parse_args()
    from repro.common import compile_cache
    compile_cache.enable()
    if args.suite:
        _run_suite(args)
        return
    if args.compare:
        ap.error("--compare requires --suite")
    print("name,us_per_call,derived")
    failed = 0
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        try:
            import importlib
            mod = importlib.import_module(modname)
            kwargs = {}
            if "include_interp" in inspect.signature(mod.run).parameters:
                kwargs["include_interp"] = args.include_interp
            for row in mod.run(**kwargs):
                d = str(row.get("derived", "")).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']:.1f},{d}",
                      flush=True)
        except Exception:
            failed += 1
            traceback.print_exc(file=sys.stderr)
            print(f"{modname},0.0,ERROR", flush=True)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
