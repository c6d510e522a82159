"""Serving cells: the program's ``ServeEngine`` under an open-loop or
backlog mix, checked against the float32 reference.

Set-up makes the weights on the device from the seed (``reference.olmo``),
builds one engine with the mix's slots and KV length, and runs one short
wave, which compiles the decode step at the window's shapes. The window
then submits each request when it is due and runs the engine one wave at a
time (``submit``, then ``run(max_waves=1)``), the only admission the engine
has. Poisson mixes arrive over ``--seconds`` and are served to the last;
a backlog mix starts waves until ``--seconds`` have passed and finishes the
last one. Each request is timed from when it was due to the return of the
wave that completed it.

After the window: the peak memory is read, the program's state is freed,
and a sample of the completed requests, drawn from the seed and holding the
one with the most generated tokens, is run through the reference. The
number compared is the widest gap by which a served token's reference logit
lies below the reference's best logit at that position.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import traffic


def program_config(cfg: dict):
    """The program's ``ModelConfig``, at the sizes of the bench's file."""
    from repro.configs.base import get_config
    return dataclasses.replace(
        get_config(cfg["assumed"]["program_arch"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"])


def setup(cfg: dict, mix: dict, seed: int):
    """Weights from the seed, on the device, and one engine with the mix's
    slots and KV length, warmed by one short wave; returns (engine,
    params)."""
    import jax
    from bench.reference import olmo as ref
    from repro.models.model import RuntimeFlags
    from repro.serve.engine import Request, ServeEngine
    params = ref.make_weights(cfg, seed)
    engine = ServeEngine(program_config(cfg), params, max_len=mix["kv_len"],
                         slots=mix["slots"],
                         flags=RuntimeFlags(use_pallas=True, remat=False))
    engine.submit(Request(rid=-1, prompt=np.zeros(2, np.int32),
                          max_new_tokens=2))
    engine.run(max_waves=1)
    engine.finished.clear()
    jax.block_until_ready(params)
    return engine, params


def serve_window(ctx, engine, reqs: list) -> dict:
    """Drive ``engine`` through the window; returns rid -> completion time
    (seconds after the window opened) and the number attempted."""
    import jax
    from repro.serve.engine import Request
    backlog = ctx.mix["arrivals"]["kind"] == "backlog"
    done, i, n = {}, 0, len(reqs)
    ctx.open_window()
    while True:
        now = ctx.clock()
        while i < n and reqs[i]["due"] <= now:
            engine.submit(Request(rid=i, prompt=reqs[i]["prompt"],
                                  max_new_tokens=reqs[i]["max_new"]))
            i += 1
        if engine.queue:
            if backlog and now >= ctx.seconds:
                break
            before = len(engine.finished)
            with jax.profiler.TraceAnnotation("bench.wave"):
                engine.run(max_waves=1)
            t = ctx.clock()
            for r in engine.finished[before:]:
                done[r.rid] = t
        elif i < n:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, reqs[i]["due"] - now))
        else:
            break
    ctx.close_window()
    attempted = len(done) if backlog else n
    return done, attempted


def check_sample(ctx, finished: list) -> list:
    """The requests the reference checks: a sample drawn from the seed that
    holds the one with the most generated tokens."""
    k = min(ctx.mix["check"]["requests"], len(finished))
    longest = max(finished, key=lambda r: len(r.generated))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([int(ctx.seed), 1])
    pick = rng.choice(len(rest), size=max(k - 1, 0), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def logit_gaps(cfg: dict, mix: dict, seed: int, sample: list,
               control: bool = False) -> dict:
    """Reference gaps of every served token of ``sample`` (and, with
    ``control``, of the fp8 pass's first choice at the same positions)."""
    from bench.reference import olmo as ref
    w = ref.make_weights(cfg, seed)
    block, width = mix["check"]["block"], mix["kv_len"]
    served, ctl = [], []
    for b0 in range(0, len(sample), block):
        rows = sample[b0:b0 + block]
        toks = np.zeros((block, width), np.int32)
        for j, r in enumerate(rows):
            seq = np.concatenate([r.prompt, np.asarray(r.generated,
                                                       np.int32)])
            toks[j, :len(seq)] = seq
        g = ref.gaps(cfg, w, toks, control=control)
        for j, r in enumerate(rows):
            lo, hi = len(r.prompt) - 1, len(r.prompt) - 1 + len(r.generated)
            served.append(g["served"][j, lo:hi])
            if control:
                ctl.append(g["control"][j, lo:hi])
    del w
    out = {"served": np.concatenate(served)}
    if control:
        out["control"] = np.concatenate(ctl)
    return out


def run(ctx) -> dict:
    cfg, mix = ctx.cfg, ctx.mix
    engine, params = setup(cfg, mix, ctx.seed)
    reqs = traffic.requests(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])

    done, attempted = serve_window(ctx, engine, reqs)

    finished = [r for r in engine.finished if r.rid in done]
    memory_peak = (ctx.device.memory_stats() or {}).get("peak_bytes_in_use")
    del engine, params
    gc.collect()

    lat = np.array([done[r.rid] - reqs[r.rid]["due"] for r in finished])
    gen = np.array([len(r.generated) for r in finished])
    elapsed = max(done.values())
    short = sum(len(r.generated) != r.max_new_tokens for r in finished)
    ctx.records = {"requests": [(len(r.prompt), len(r.generated),
                                 reqs[r.rid]["due"], done[r.rid])
                                for r in finished],
                   "elapsed_s": elapsed}

    sample = check_sample(ctx, finished)
    gaps = logit_gaps(cfg, mix, ctx.seed, sample)["served"]
    limit = mix["check"]["max_logit_gap"]
    widest = float(np.max(gaps))
    return {
        "attempted": attempted, "failed": attempted - len(finished) + short,
        "memory_peak_bytes": memory_peak,
        "metrics": {
            "request_latency_p90_ms": float(np.percentile(lat, 90)) * 1e3,
            "output_tokens_per_s": float(gen.sum()) / elapsed,
        },
        "checks": {"max_logit_gap": {"value": widest, "limit": limit,
                                     "ok": widest <= limit},
                   "tokens_checked": {"value": int(gaps.size),
                                      "limit": mix["check"]["min_tokens"],
                                      "ok": gaps.size
                                      >= mix["check"]["min_tokens"]}},
    }
