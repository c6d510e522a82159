"""Records the LFM2 cell's short wave that ``bench/tests/test_lfm2_trace.py``
reads: the cell's engine (256 slots, KV length 384, weights from the seed),
warmed as in a run, then one traced wave of 256 requests with two prompt
tokens and three answer tokens (four decode steps), with the program's
counters of the held experts' work.

    python3 bench/tests/data/v5e_lfm2_wave/record.py --out <dir>

writes ``<dir>/v5e_lfm2_wave.json`` and the trace under ``<dir>/trace``. It
needs a TPU.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[4]
CELL = "lfm2-8b-a1b.serve.batch"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=3000175101)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np
    from bench import run
    from bench.harness import serve_lfm2
    from repro.serve.engine import Request
    run._device_check(1)
    run._compile_cache()
    cell = run.load_cell(CELL)
    engine, _ = serve_lfm2.setup(cell["config"], cell["mix"], args.seed)
    waves = serve_lfm2.count_steps(engine)
    rng = np.random.default_rng(args.seed)
    slots = cell["mix"]["slots"]
    for rid in range(slots):
        engine.submit(Request(rid=rid, max_new_tokens=3, prompt=rng.integers(
            0, cell["config"]["vocab_size"], 2).astype(np.int32)))
    out = pathlib.Path(args.out)
    jax.profiler.start_trace(str(out / "trace"))
    t0 = time.perf_counter()
    engine.run(max_waves=1)
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    records = dict(serve_lfm2.expert_totals(waves), window_s=window_s,
                   requests=len(engine.finished))
    (out / "v5e_lfm2_wave.json").write_text(json.dumps(records) + "\n")
    print(json.dumps(records))


if __name__ == "__main__":
    main()
