"""LFM2-8B-A1B serving at one chip's expert share: the program's
``ServeEngine`` under a backlog mix, checked against the float32 reference
(``bench.reference.lfm2``).

Set-up makes the weights on the device from the seed, builds one engine
with the mix's slots and KV length and runs one short wave, which compiles
the decode step. The window is ``bench.harness.serve``'s: waves start until
``--seconds`` have passed and the last is finished. The harness counts the
engine's decode steps (its calls of the jitted step) and, from the counters
the program keeps in each wave's cache, the rows the held experts computed
and the (call, expert) pairs that had rows, which ``moe_roofline`` reads. After
the window the program's state is freed and a sample of the completed
requests, drawn from the seed and holding the one with the most generated
tokens, is run through the reference, as in the OLMo cell.

    python3 -m bench.harness.serve_lfm2 --seeds 1,2,3 [--seconds 8]

reads the limit of ``max_logit_gap`` on the chip: for each seed, a short
window and then, on the same answers, the program's widest gap and the fp8
control's (``logit_gaps(..., control=True)``), as ``bench/controls.py``
does for the OLMo cell.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys

import numpy as np

from bench import traffic
from bench.harness import require
from bench.harness.serve import check_sample, serve_window


def program_config(cfg: dict):
    """The program's ``ModelConfig``, at the sizes of the bench's file."""
    from bench.reference.lfm2 import mixer_kinds
    from repro.configs.base import ARCH_IDS, get_config
    arch = cfg["assumed"]["program_arch"]
    require(arch in ARCH_IDS, f"the program has no configuration {arch!r}")
    # the program's sigmoid router: top k of score + bias, normalised, x 1
    require(cfg["use_expert_bias"] and cfg["norm_topk_prob"]
            and cfg["routed_scaling_factor"] == 1,
            "the program routes by biased sigmoid scores, normalised, "
            "scale 1")
    base = get_config(arch)
    h = cfg["num_attention_heads"]
    return dataclasses.replace(
        base, num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=h, kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // h, d_ff=cfg["moe_intermediate_size"],
        dense_d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["norm_eps"],
        conv_width=cfg["conv_L_cache"], block_pattern=tuple(mixer_kinds(cfg)),
        num_dense_layers=cfg["num_dense_layers"], tie_embeddings=True,
        moe=dataclasses.replace(
            base.moe, num_experts=cfg["num_experts_published"],
            top_k=cfg["num_experts_per_tok"],
            held=cfg["num_experts"], first_held=cfg["first_expert"]))


def setup(cfg: dict, mix: dict, seed: int):
    """Weights from the seed, on the device, and one engine with the mix's
    slots and KV length, warmed by one short wave; returns (engine,
    params)."""
    import jax
    from bench.reference import lfm2 as ref
    from repro.models.model import RuntimeFlags
    from repro.serve.engine import Request, ServeEngine
    pcfg = program_config(cfg)
    params = ref.make_weights(cfg, seed)
    engine = ServeEngine(pcfg, params, max_len=mix["kv_len"],
                         slots=mix["slots"],
                         flags=RuntimeFlags(use_pallas=True, remat=False))
    engine.submit(Request(rid=-1, prompt=np.zeros(2, np.int32),
                          max_new_tokens=2))
    engine.run(max_waves=1)
    engine.finished.clear()
    jax.block_until_ready(params)
    return engine, params


def count_steps(engine) -> list:
    """Counts the engine's calls of its decode step from now on, and keeps
    the held experts' counters of the cache each wave ends with (the
    program's own: ``expert_rows`` and ``expert_calls`` of every layer that
    holds experts, summed since the wave's cache was made). Returns a list
    with one entry a wave: [calls, counters]; ``expert_totals`` adds them
    up once the window is over, so the window reads nothing back."""
    waves, step = [], engine._decode

    def counted(params, cache, tokens, pos):
        if pos == 0:                       # a wave's first step
            waves.append([0, None])
        logits, cache = step(params, cache, tokens, pos)
        waves[-1][0] += 1
        # in layer order (a jitted step hands its dict back sorted by key)
        waves[-1][1] = [(e["expert_rows"], e["expert_calls"]) for _, e in
                        sorted((int(k.rsplit("_", 1)[1]), e)
                               for k, e in cache.items()
                               if "expert_rows" in e)]
        return logits, cache
    engine._decode = counted
    return waves


def expert_totals(waves: list) -> dict:
    """Decode calls, and the rows and the (call, expert) pairs with rows,
    over the waves ``count_steps`` kept: in all, and per held-expert layer
    in layer order (``*_by_layer``)."""
    layers = max((len(c) for _, c in waves), default=0)
    rows, used = [0] * layers, [0] * layers
    for _, c in waves:
        for i, (r, u) in enumerate(c):
            rows[i] += int(np.asarray(r).sum())
            used[i] += int(np.asarray(u).sum())
    return {"decode_calls": sum(n for n, _ in waves),
            "expert_rows": sum(rows), "expert_reads": sum(used),
            "expert_rows_by_layer": rows, "expert_reads_by_layer": used}


def logit_gaps(cfg: dict, mix: dict, seed: int, sample: list,
               control: bool = False) -> dict:
    """Reference gaps of every served token of ``sample`` (and, with
    ``control``, of the fp8 pass's first choice at the same positions)."""
    from bench.reference import lfm2 as ref
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


def _serve(ctx):
    """Set-up and the window; returns (finished, done, attempted, calls)
    with the program's state freed."""
    engine, params = setup(ctx.cfg, ctx.mix, ctx.seed)
    waves = count_steps(engine)
    reqs = traffic.requests(ctx.mix, ctx.seed, ctx.seconds,
                            ctx.cfg["vocab_size"])
    done, attempted = serve_window(ctx, engine, reqs)
    finished = [r for r in engine.finished if r.rid in done]
    ctx.records["memory_peak_bytes"] = (
        ctx.device.memory_stats() or {}).get("peak_bytes_in_use")
    ctx.records.update(expert_totals(waves))
    del engine, params, waves
    gc.collect()
    return finished, done, attempted


def run(ctx) -> dict:
    cfg, mix = ctx.cfg, ctx.mix
    finished, done, attempted = _serve(ctx)
    gen = np.array([len(r.generated) for r in finished])
    short = sum(len(r.generated) != r.max_new_tokens for r in finished)
    ctx.records["elapsed_s"] = max(done.values())

    sample = check_sample(ctx, finished)
    gaps = logit_gaps(cfg, mix, ctx.seed, sample)["served"]
    limit = mix["check"]["max_logit_gap"]
    widest = float(np.max(gaps))
    return {
        "attempted": attempted, "failed": attempted - len(finished) + short,
        "memory_peak_bytes": ctx.records["memory_peak_bytes"],
        "metrics": {"output_tokens_per_s":
                    float(gen.sum()) / ctx.records["elapsed_s"]},
        "checks": {"max_logit_gap": {"value": widest, "limit": limit,
                                     "ok": widest <= limit},
                   "tokens_checked": {"value": int(gaps.size),
                                      "limit": mix["check"]["min_tokens"],
                                      "ok": gaps.size
                                      >= mix["check"]["min_tokens"]}},
    }


def readings(name: str, seeds: list, seconds: float) -> list:
    """The program's widest gap and the fp8 control's, per seed, each on
    the cell's sample of one short window."""
    from bench import run as bench_run
    cell = bench_run.load_cell(name)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    chips = cell["cell"]["chips"]
    device = bench_run._device_check(chips)
    bench_run._compile_cache()
    out = []
    for seed in seeds:
        ctx = bench_run.Context(cell, seed, seconds, False, device, chips)
        finished, _, _ = _serve(ctx)
        g = logit_gaps(ctx.cfg, ctx.mix, seed, check_sample(ctx, finished),
                       control=True)
        out.append({"seed": seed, "program": float(g["served"].max()),
                    "control": float(g["control"].max()),
                    "tokens": int(g["served"].size)})
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="lfm2-8b-a1b.serve.batch")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    rs = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                  args.seconds)
    print(json.dumps({"workload": args.workload,
                      "lower": max(r["program"] for r in rs),
                      "upper": min(r["control"] for r in rs)}), flush=True)


if __name__ == "__main__":
    main()
