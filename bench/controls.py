"""Readings that set a cell's limit: the program's, and its control's.

    python3 bench/controls.py --workload <cell> --seeds 1,2,3 [--seconds 8]

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, and then, on the same answers, the number the cell
compares (the program's reading) and the same number for the control,
which is the reference computed in the nearest precision below the one the
configuration states (fp8 for bfloat16). The limit lies between the largest
program reading and the smallest control reading. The benchmark's own runs
never run this; it needs a TPU, like a run.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def serve_readings(ctx) -> dict:
    """Widest served-token gap and widest control gap, on the cell's
    sample of one short window."""
    from bench import traffic
    from bench.harness import serve
    engine, params = serve.setup(ctx.cfg, ctx.mix, ctx.seed)
    reqs = traffic.requests(ctx.mix, ctx.seed, ctx.seconds,
                            ctx.cfg["vocab_size"])
    done, _ = serve.serve_window(ctx, engine, reqs)
    finished = [r for r in engine.finished if r.rid in done]
    del engine, params
    gc.collect()
    sample = serve.check_sample(ctx, finished)
    g = serve.logit_gaps(ctx.cfg, ctx.mix, ctx.seed, sample, control=True)
    return {"program": float(g["served"].max()),
            "control": float(g["control"].max()),
            "tokens": int(g["served"].size)}


def kernel_readings(ctx, programs) -> dict:
    """Worst relative error of the program and of the fp8 reference, over
    the problems, on the seed's operands."""
    import jax
    from bench.harness import kernels
    from bench.reference import l2 as ref
    from repro.core.executor import run_program
    prog, ctl = {}, {}
    for p in ctx.cfg["problems"]:
        name = p["name"]
        ops = kernels.operands(ctx.cfg, p, ctx.seed)
        ins, params = kernels.split(programs[name], ops)
        (_, got), = run_program(programs[name], ins, params).items()
        want = ref.output(name, ops)
        prog[name] = kernels.rel_error(got, want)
        ctl[name] = kernels.rel_error(ref.output(name, ops, quant="fp8"),
                                      want)
        jax.block_until_ready(want)
    return {"program": max(prog.values()), "control": max(ctl.values()),
            "by_problem": {n: [prog[n], ctl[n]] for n in prog}}


def readings(name: str, seeds: list, seconds: float) -> list:
    from bench import run
    cell = run.load_cell(name)
    sys.path.insert(0, str(ROOT / "src"))
    device = run._device_check(cell["cell"]["chips"])
    run._compile_cache()
    out = []
    programs = None
    for seed in seeds:
        ctx = run.Context(cell, seed, seconds, False, device,
                          cell["cell"]["chips"])
        if cell["mix"]["harness"] == "serve":
            r = serve_readings(ctx)
        else:
            if programs is None:
                from bench.harness import kernels
                programs = kernels.forge_programs(cell["config"], run.CACHE)
            r = kernel_readings(ctx, programs)
        out.append(dict(seed=seed, **r))
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    rs = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                  args.seconds)
    print(json.dumps({"workload": args.workload,
                      "lower": max(r["program"] for r in rs),
                      "upper": min(r["control"] for r in rs)}), flush=True)


if __name__ == "__main__":
    main()
