"""Serving driver: batched greedy generation with ``ServeEngine``.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced

Without ``--reduced`` the config runs at its published widths. Weights are
random (seeded) bf16, and the KV cache shares their dtype.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, ModelConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import RuntimeFlags, init_params
from repro.serve.engine import Request, ServeEngine

# KV lengths are rounded up to a multiple of 128, so the decode kernel's
# KV blocks tile the cache without a ragged tail
KV_ALIGN = 128


def kv_len(prompt_len: int, max_new: int) -> int:
    need = prompt_len + max_new
    return -(-need // KV_ALIGN) * KV_ALIGN


def make_params(cfg: ModelConfig, seed: int = 0):
    """Seeded random bf16 weights, made on the device."""
    return jax.jit(init_params, static_argnums=(0, 2))(
        cfg, jax.random.PRNGKey(seed), jnp.bfloat16)


def serve(cfg: ModelConfig, params, *, requests: int, prompt_len: int,
          max_new: int, slots: int, use_pallas: bool = False,
          seed: int = 0) -> List[Request]:
    """Serve ``requests`` seeded random prompts; returns them finished."""
    engine = ServeEngine(cfg, params, max_len=kv_len(prompt_len, max_new),
                         slots=slots,
                         flags=RuntimeFlags(use_pallas=use_pallas,
                                            remat=False))
    rng = np.random.default_rng(seed)
    for i in range(requests):
        engine.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, prompt_len),
            max_new_tokens=max_new))
    return engine.run()


def main(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list(ARCH_IDS))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the smoke-scale config instead of the "
                         "published widths")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=3)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = make_params(cfg)
    t0 = time.time()
    finished = serve(cfg, params, requests=args.requests,
                     prompt_len=args.prompt_len, max_new=args.max_new,
                     slots=args.slots)
    dt = time.time() - t0
    total_new = sum(len(r.generated) for r in finished)
    print(json.dumps({
        "arch": args.arch, "reduced": args.reduced,
        "platform": jax.devices()[0].platform,
        "finished": len(finished), "new_tokens": total_new,
        "wall_s_with_compile": round(dt, 3),
        "sample": finished[0].generated[:8] if finished else [],
    }, indent=2))
    assert len(finished) == args.requests


if __name__ == "__main__":
    main()
