"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything else is found by name: the cell in ``BENCHMARK.json``, its mix in
``bench/workloads/<cell>.json`` (which names its harness in
``bench/harness/``), its configuration file, and one reader per per-layer
metric in ``bench/metrics/<metric>.py``. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``, each
number compared beside its limit (also printed as the last lines of
standard error).

The run needs a TPU: when JAX's first device is not one, or there are fewer
chips than the cell asks for, it exits non-zero and prints no result. It
never falls back to the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = BENCH / ".cache"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_spec(root: pathlib.Path = ROOT) -> dict:
    """``BENCHMARK.json``, with the cells of ``bench/pending.json`` after
    its own: cells whose files are built but whose limits and bounds have
    not yet been read on the chip. A pending cell runs like any other; the
    benchmark's checks see only ``BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    pending = root / "bench" / "pending.json"
    if pending.is_file():
        extra = json.loads(pending.read_text())
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            have = {e["name"] for e in spec[key]}
            spec[key] += [e for e in extra.get(key, [])
                          if e["name"] not in have]
    return spec


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry, configuration, mix and metric lists, by name."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json or "
                       f"bench/pending.json")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return {"cell": cell,
            "config": json.loads((root / conf["file"]).read_text()),
            "mix": json.loads((root / "bench" / "workloads"
                               / f"{name}.json").read_text()),
            "end_to_end": e2e, "per_layer": per_layer}


def read_metric(name: str, ctx) -> float | None:
    """Run the reader ``bench/metrics/<name>.py``; None where it finds
    nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Context:
    """What a harness gets, and what it hands back to the readers."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 device, chips: int):
        self.name = cell["cell"]["name"]
        self.cfg, self.mix = cell["config"], cell["mix"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.device, self.chips = device, chips
        self.t0 = None              # window start, perf_counter seconds
        self.window_end = None      # window end (last completion)
        self.records: dict = {}     # harness output the readers use
        self.trace_summary = None   # bench.trace.Summary of the window
        self._trace_dir = CACHE / "trace" / self.name

    def clock(self) -> float:
        """Seconds since the window opened."""
        return time.perf_counter() - self.t0

    def open_window(self):
        if self.trace:
            import shutil
            import jax
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self._trace_dir))
        self.t0 = time.perf_counter()

    def close_window(self):
        self.window_end = time.perf_counter()
        if self.trace:
            import jax
            from bench import trace as tr
            jax.profiler.stop_trace()
            self.trace_summary = tr.summarize(
                tr.load(self._trace_dir), self.window_end - self.t0,
                self.chips)


def _device_check(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees "
                     f"{len(devices)}")
    return devices[0]


def _compile_cache():
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run_cell(name: str, seed: int, seconds: float, trace: bool) -> dict:
    """One run of one cell; returns the result object."""
    cell = load_cell(name)
    chips = cell["cell"]["chips"]
    if not (ROOT / "src" / "repro").is_dir():
        raise NoChip(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    device = _device_check(chips)
    _compile_cache()
    from bench import peaks as pk
    ctx = Context(cell, seed, seconds, trace, device, chips)
    ctx.peaks = pk.peaks_for(device.device_kind)
    harness = importlib.import_module(f"bench.harness.{ctx.mix['harness']}")
    out = harness.run(ctx)
    setup_s = ctx.t0 - T_START

    if trace:
        metrics = {}
        for m in cell["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    checks = out["checks"]
    result = {"correct": all(c["ok"] for c in checks.values())
              and out["failed"] == 0,
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics,
              "device": {"platform": device.platform,
                         "kind": device.device_kind, "count": chips,
                         "memory_peak_bytes": out["memory_peak_bytes"]}}
    if trace:
        s = ctx.trace_summary
        result["device"].update(busy_s=s.busy_s, window_s=s.window_s)
        result["breakdown"] = {"device_ops": s.top_ops(10),
                               "idle_gaps": s.top_gaps(10)}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
    gc.collect()
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
