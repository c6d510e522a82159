"""Optimizer cells: Forge tunes each Level-2 problem, and the window times
the accepted bench programs on the chip.

Set-up runs Forge (``thread`` backend, one worker) on every problem of the
configuration, with its result store at a fixed path under
``bench/.cache/``: the first run in a checkout optimizes and verifies on
the chip, later runs replay the store. It then makes each problem's
operands on the device from the seed and calls every accepted program
``WARM_ROUNDS`` times round-robin, in the loop that then runs the window,
which compiles everything the window runs.

The window calls the programs round-robin through
``core.executor.run_program``, each call ending in ``block_until_ready``,
until ``--seconds`` have passed. Per problem, the host time of its calls is
divided by their number; ``kernel_us_geomean`` is the geometric mean over
problems. One call per problem, at a time in the window drawn from the
seed, keeps its output; after the window each kept output is compared with
the float32 reference on the same operands. Standard error gets the seconds
of the window's first, median, slowest and last round: a first round far
above the median means that something compiled in the window.
"""

from __future__ import annotations

import gc
import math
import statistics
import sys
import time

import numpy as np

from bench.harness import require
from bench.reference import l2 as ref

STORE = "forge_store.json"
WARM_ROUNDS = 2


def operands(cfg: dict, problem: dict, seed: int) -> dict:
    """Seeded float32 operands on the device: weights N(0, 1/K), the rest
    N(0, 1)."""
    import jax
    import jax.numpy as jnp
    from bench.reference.olmo import seed_words, seeded_key
    dims = cfg["dims"]
    key = jax.random.fold_in(seeded_key(jnp.asarray(seed_words(seed))),
                             cfg["problems"].index(problem))
    out = {}
    for i, (name, shape) in enumerate(sorted(problem["operands"].items())):
        std = 1.0 / math.sqrt(dims["K"]) if name == "w" else 1.0
        out[name] = jax.random.normal(jax.random.fold_in(key, i),
                                      tuple(dims[d] for d in shape),
                                      jnp.float32) * std
    return out


def forge_programs(cfg: dict, store_dir) -> dict:
    """Forge's accepted bench program for every problem, by name."""
    from repro.aibench.runner import KernelRunner
    from repro.aibench.spec import load_specs
    from repro.core.config import ForgeConfig
    from repro.forge import Forge
    specs = {s.name: s for s in load_specs()}
    for p in cfg["problems"]:
        require(specs[p["name"]].dims("bench") == cfg["dims"],
                 f"{p['name']}: the program's bench dims "
                 f"{specs[p['name']].dims('bench')} are not {cfg['dims']}")
    store_dir.mkdir(parents=True, exist_ok=True)
    config = ForgeConfig(execution_backend="thread", workers=1,
                         cache_path=str(store_dir / STORE))
    with Forge(config) as forge:
        runner = KernelRunner(forge=forge)
        report = forge.optimize_batch([runner.make_job(specs[p["name"]])
                                       for p in cfg["problems"]])
    return {r.job.name: r.result.bench_program for r in report.results}


def split(program, ops: dict) -> tuple:
    """The operands as the program's (inputs, params), checked against the
    graph's names and shapes."""
    g = program.graph
    want = {n.name: tuple(n.shape) for n in g.inputs() + g.params()}
    have = {k: tuple(v.shape) for k, v in ops.items()}
    require(want == have, f"{program.name}: the program's operands {want} "
             f"are not the configuration's {have}")
    names = {n.name for n in g.inputs()}
    return ({k: v for k, v in ops.items() if k in names},
            {k: v for k, v in ops.items() if k not in names})


def rel_error(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return math.inf
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def run(ctx) -> dict:
    import jax
    from repro.core.executor import run_program
    from bench.run import CACHE
    cfg, mix = ctx.cfg, ctx.mix
    programs = forge_programs(cfg, CACHE)
    problems = cfg["problems"]
    args = {}
    for p in problems:
        args[p["name"]] = split(programs[p["name"]],
                                operands(cfg, p, ctx.seed))
    rng = np.random.default_rng([int(ctx.seed), 2])
    keep_at = {p["name"]: float(rng.uniform(0, 0.9 * ctx.seconds))
               for p in problems}
    kept, total, calls = {}, {p["name"]: 0.0 for p in problems}, \
        {p["name"]: 0 for p in problems}

    # Warm-up and window are one loop, so that every call of run_program
    # comes from this one place. A Pallas kernel's serialized body holds the
    # Python stack it was traced from, lines and columns, and so does the key
    # of JAX's compilation cache: the same program called from another place
    # compiles anew. Each frame more on that stack also lengthens every call.
    window, rounds = False, []
    while True:
        if not window and len(rounds) == WARM_ROUNDS:
            window, rounds = True, []
            ctx.open_window()
        # a round more where a round is long next to the window (the CPU
        # tests)
        if (window and ctx.clock() >= ctx.seconds
                and len(kept) == len(problems)):
            break
        t_round = time.perf_counter()
        for p in problems:
            name = p["name"]
            with jax.profiler.TraceAnnotation(f"bench.call.{name}"):
                t = time.perf_counter()
                out = run_program(programs[name], *args[name])
                jax.block_until_ready(out)
                took = time.perf_counter() - t
            if window:
                total[name] += took
                calls[name] += 1
                if name not in kept and t - ctx.t0 >= keep_at[name]:
                    kept[name] = out
        rounds.append(time.perf_counter() - t_round)
    ctx.close_window()
    memory_peak = (ctx.device.memory_stats() or {}).get("peak_bytes_in_use")
    ctx.records = {"calls": calls, "host_s": total}
    print(f"bench: {len(rounds)} rounds of {len(problems)} calls, seconds "
          f"first {rounds[0]!r} median {statistics.median(rounds)!r} "
          f"max {max(rounds)!r} last {rounds[-1]!r}", file=sys.stderr)
    del programs
    gc.collect()

    errors = {}
    for p in problems:
        name = p["name"]
        (_, value), = kept[name].items()
        ops = dict(args[name][0], **args[name][1])
        errors[name] = rel_error(value, ref.output(name, ops))
    ctx.records["errors"] = errors
    worst = max(errors.values())
    limit = mix["check"]["max_rel_error"]
    us = [total[n] / calls[n] * 1e6 for n in total]
    return {
        "attempted": sum(calls.values()), "failed": 0,
        "memory_peak_bytes": memory_peak,
        "metrics": {"kernel_us_geomean":
                    math.exp(sum(map(math.log, us)) / len(us))},
        "checks": {"max_rel_error": {"value": worst, "limit": limit,
                                     "ok": worst <= limit}},
    }
