"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --variant smoke --steps 200 --method ether --ckpt-dir /tmp/run1

Defaults run the paper's regime: frozen base + ETHER adapters, AdamW
(no weight decay — paper App. C.4), cosine schedule with warmup, high
LR (ETHER's LR-robustness is the point), checkpoint/auto-resume on.
On a real pod, pass --mesh data,model sizes; on CPU this trains the
smoke configs end-to-end (examples/train_smollm.py drives it).
"""

from __future__ import annotations

import argparse
import dataclasses
import os


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    # no static choices= — the valid set lives in ONE place
    # (repro.core.methods.available()); run() resolves the name there so
    # a bad value gets a typed UnknownMethodError listing every valid
    # method, and --help stays jax-free
    ap.add_argument("--method", default="ether",
                    help="PEFT method name (repro.core.methods.available())")
    ap.add_argument("--n-blocks", type=int, default=32)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--peft-mode", default="activation",
                    choices=["activation", "weight", "blockgemm"])
    ap.add_argument("--backend", default="auto",
                    choices=("jnp", "pallas", "auto"),
                    help="execution backend for the adapter hot ops "
                         "(core.execute; auto = Pallas where shapes tile)")
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "constant"])
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--restore", default="auto", choices=["auto", "none"])
    ap.add_argument("--mesh", default=None,
                    help="data,model device grid, e.g. 4,2")
    ap.add_argument("--log", default=None, help="metrics JSONL path")
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="failure injection (fault-tolerance tests)")
    return ap


def run(args) -> dict:
    # deferred imports: --help must not initialize jax
    from repro.configs import get_config, peft_targets
    from repro.core import methods
    from repro.core.transforms import PEFTConfig
    from repro.data.pipeline import make_stream
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw, constant, cosine, wsd
    from repro.runtime.trainer import Trainer

    cfg = get_config(args.arch, args.variant)
    methods.get(args.method)   # typed UnknownMethodError on bad names
    full_ft = args.method == "full"
    peft = None if full_ft else PEFTConfig(
        method=args.method, n_blocks=args.n_blocks, rank=args.rank,
        alpha=float(args.rank), mode=args.peft_mode,
        targets=peft_targets(args.arch), backend=args.backend)

    sched = {"cosine": lambda: cosine(args.lr, args.steps, args.warmup),
             "wsd": lambda: wsd(args.lr, args.steps, args.warmup),
             "constant": lambda: constant(args.lr)}[args.schedule]()
    opt = adamw(sched, weight_decay=args.weight_decay)

    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_host_mesh(d, m)

    stream = make_stream(
        args.data, vocab=cfg.vocab, batch=args.batch, seq_len=args.seq_len,
        seed=args.seed, **({"path": args.data_path}
                           if args.data == "binary" else {}))

    trainer = Trainer(cfg, peft, opt, mesh=mesh, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, restore=args.restore,
                      full_finetune=full_ft, seed=args.seed,
                      log_path=args.log, fail_at_step=args.fail_at_step)
    metrics = trainer.fit(stream, steps=args.steps)
    print(f"done @ step {trainer.step}: {metrics}")
    return metrics


def main():
    from repro.common import compile_cache
    args = build_argparser().parse_args()
    compile_cache.enable()
    run(args)


if __name__ == "__main__":
    main()
