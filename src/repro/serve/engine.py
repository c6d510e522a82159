"""Batched serving engine: prompt prefill + greedy decode over a slot batch.

Admission is batch-synchronous (a wave of equal-length prompts fills the
slots, decodes in lockstep, then the next wave admits) — the slot/cache
plumbing that a continuous-batching scheduler would drive. The KV cache is
kept in the parameters' dtype (bf16 at published width); the multi-pod
serving path (sharded caches, split-KV decode) is exercised by the dry-run
cells rather than this single-device engine.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.model import RuntimeFlags, decode_step, init_cache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S] int32 (equal length within a wave)
    max_new_tokens: int = 16
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # float32 logits after the last prompt token (they pick generated[0])
    prompt_logits: Optional[np.ndarray] = None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_len: int = 256,
                 slots: int = 4,
                 flags: RuntimeFlags = RuntimeFlags(remat=False)):
        self.cfg = cfg
        self.params = params
        self.flags = flags
        self.max_len = max_len
        self.slots = slots
        # deque: wave admission pops from the head (popleft is O(1); the
        # old list.pop(0) shifted the whole backlog per admitted request)
        self.queue: Deque[Request] = collections.deque()
        self.finished: List[Request] = []
        self._decode = jax.jit(
            lambda p, c, t, pos: decode_step(cfg, p, c, t, pos, flags),
            static_argnums=())

    def submit(self, req: Request):
        self.queue.append(req)

    # ------------------------------------------------------------------
    def _run_wave(self):
        wave = [self.queue.popleft()
                for _ in range(min(self.slots, len(self.queue)))]
        if not wave:
            return
        plen = len(wave[0].prompt)
        assert all(len(r.prompt) == plen for r in wave), \
            "wave admission requires equal-length prompts"
        cache = init_cache(self.cfg, self.slots, self.max_len,
                           self.params["embed"].dtype)
        # prefill via lockstep decode steps (slot-batched)
        logits = None
        for t in range(plen):
            toks = np.zeros((self.slots, 1), np.int32)
            for i, r in enumerate(wave):
                toks[i, 0] = int(r.prompt[t])
            logits, cache = self._decode(self.params, cache,
                                         jnp.asarray(toks), t)
        # greedy decode
        pos = plen
        alive = list(range(len(wave)))
        logits = np.asarray(logits)
        for i, r in enumerate(wave):
            r.prompt_logits = logits[i]
        nxt = np.argmax(logits, axis=-1)
        while alive and pos < self.max_len:
            toks = np.zeros((self.slots, 1), np.int32)
            for i in alive:
                wave[i].generated.append(int(nxt[i]))
                toks[i, 0] = int(nxt[i])
            alive = [i for i in alive
                     if len(wave[i].generated) < wave[i].max_new_tokens]
            if not alive:
                break
            logits, cache = self._decode(self.params, cache,
                                         jnp.asarray(toks), pos)
            nxt = np.argmax(np.asarray(logits), axis=-1)
            pos += 1
        for r in wave:
            r.done = True
            self.finished.append(r)

    # ------------------------------------------------------------------
    def run(self, max_waves: int = 64) -> List[Request]:
        waves = 0
        while self.queue and waves < max_waves:
            self._run_wave()
            waves += 1
        return self.finished
