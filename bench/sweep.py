"""Knee sweep of a serving cell: one set-up, then one window per rate.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4,5 \
        [--seconds 30] [--seed 1]

The cell is a serving cell of ``BENCHMARK.json`` whose mix has Poisson
arrivals; the sweep replaces the mix's rate by each rate in turn.

For each rate, the cell's mix at that rate is served as a run serves it,
and the line printed gives the p50 and p90 latency, the requests served, and
the drain: how long after the last arrival the last request completed. Below
the knee the drain stays about one wave; above it the queue grows through
the window and the drain with it. It is run once, on the chip, when a
serving cell is defined; the rate chosen goes into the cell's mix file.
"""

from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run, traffic
    from bench.harness import serve
    cell = run.load_cell(args.workload)
    device = run._device_check(cell["cell"]["chips"])
    run._compile_cache()
    cfg = cell["config"]
    engine, _ = serve.setup(cfg, cell["mix"], args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        c = copy.deepcopy(cell)
        c["mix"]["arrivals"]["rate"] = rate
        ctx = run.Context(c, args.seed, args.seconds, False, device, 1)
        engine.finished.clear()
        reqs = traffic.requests(c["mix"], args.seed, args.seconds,
                                cfg["vocab_size"])
        done, _ = serve.serve_window(ctx, engine, reqs)
        lat = np.array([done[i] - reqs[i]["due"] for i in done])
        print(json.dumps({
            "rate": rate, "requests": len(done),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p90_ms": float(np.percentile(lat, 90)) * 1e3,
            "drain_s": max(done.values()) - reqs[-1]["due"]}), flush=True)


if __name__ == "__main__":
    main()
