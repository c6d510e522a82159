"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 200 \
        --seq-len 256 --global-batch 8 --reduced --ckpt-dir /tmp/ckpt

``--reduced`` trains the smoke-scale config (CPU-runnable); full configs are
for real pods. ``--kernel-opt run`` invokes the Forge pipeline on the model's
kernel call-sites first and caches the tuned configs (DESIGN.md §3.1).
"""

from __future__ import annotations

import argparse
import json
import time


from repro.configs.base import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import RuntimeFlags
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import TrainConfig
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--kernel-opt", default="cached",
                    choices=["off", "cached", "run"])
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.kernel_opt == "run":
        from repro.launch.kernel_opt import optimize_arch_kernels
        optimize_arch_kernels(cfg, seq_len=args.seq_len,
                              batch=args.global_batch)

    tcfg = TrainConfig(microbatches=args.microbatches,
                       optimizer=AdamWConfig(lr=args.lr,
                                             total_steps=args.steps,
                                             warmup_steps=max(args.steps // 20, 5)))
    trainer = Trainer(cfg, seq_len=args.seq_len,
                      global_batch=args.global_batch,
                      flags=RuntimeFlags(remat=False,
                                         chunked_attention=args.seq_len > 2048),
                      tcfg=tcfg, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every)
    if args.resume:
        trainer.maybe_resume()

    t0 = time.time()
    history = trainer.train(args.steps)
    dt = time.time() - t0
    first = history[0]["loss"] if history else float("nan")
    last = history[-1]["loss"] if history else float("nan")
    toks = args.global_batch * args.seq_len * len(history)
    print(json.dumps({
        "arch": args.arch, "steps": len(history),
        "loss_first": round(first, 4), "loss_last": round(last, 4),
        "tokens_per_s": round(toks / dt, 1),
        "straggler_flags": trainer.straggler.flagged,
    }, indent=2))
    assert last < first, "training did not reduce the loss"


if __name__ == "__main__":
    main()
