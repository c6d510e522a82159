"""The one traffic generator: turns a mix file's parameters and a seed into
requests.

Every seed gets the same set of sizes and arrival gaps, in another order:
answer lengths are the quantiles of their distribution at evenly spaced
probabilities, and Poisson gaps the quantiles of the exponential, each
permuted by the seed. So seeds change which request is long and which gap is
short, not how much work a run holds. Prompt token ids are drawn from the
seed.

Mix parameters (``bench/workloads/<cell>.json``):

- ``prompt_len``: tokens in every prompt;
- ``answer_len``: ``{"min": a, "max": b}``, log-uniform over [a, b];
- ``arrivals``: ``{"kind": "poisson", "rate": r}`` (requests per second over
  the window, open loop) or ``{"kind": "backlog", "requests": n}`` (all due
  at the start; lengths stratified in blocks of ``slots``, so every full wave
  holds the same set of lengths).
"""

from __future__ import annotations

import math

import numpy as np


def _loguniform_quantiles(n: int, lo: int, hi: int) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + p * (math.log(hi) - math.log(lo)))
                   ).astype(int)


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Requests as dicts: ``due`` (seconds after the window opens),
    ``prompt`` (int32 array) and ``max_new``."""
    rng = np.random.default_rng(int(seed))
    arr = mix["arrivals"]
    lo, hi = mix["answer_len"]["min"], mix["answer_len"]["max"]
    if arr["kind"] == "poisson":
        n = int(round(arr["rate"] * seconds))
        p = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-p) / arr["rate"])
        due = np.cumsum(gaps) - gaps[0]
        lengths = rng.permutation(_loguniform_quantiles(n, lo, hi))
    elif arr["kind"] == "backlog":
        n, block = arr["requests"], mix["slots"]
        due = np.zeros(n)
        lengths = np.concatenate([
            rng.permutation(_loguniform_quantiles(block, lo, hi))
            for _ in range(-(-n // block))])[:n]
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    prompts = rng.integers(0, vocab, (n, mix["prompt_len"]), dtype=np.int32)
    return [{"due": float(due[i]), "prompt": prompts[i],
             "max_new": int(lengths[i])} for i in range(n)]
