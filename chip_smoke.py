"""Bring-up smoke run of the main path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded train step only

One chip, in one process (a TPU belongs to one process at a time):

1. ``forge_gemm`` — Forge (``thread`` backend, one worker) optimizes the
   ``gemm_bias_gelu`` L2 problem. Verification executes the candidates as
   compiled Pallas kernels on the chip; the phase fails unless at least one
   candidate passed the correctness level and none raised.
2. ``forge_olmo_sites`` — ``launch.kernel_opt.optimize_arch_kernels`` tunes
   OLMo-1B's GEMM call sites, under the same two conditions.
3. ``bench_gemm`` — the accepted ``gemm_bias_gelu`` program runs at its bench
   dims (4096x4096x1024) and is compared with the f32 jnp oracle.
4. ``serve_olmo`` — OLMo-1B at its published widths (bf16 weights) is served
   through ``launch.serve`` / ``ServeEngine`` with the Pallas decode kernel;
   each request's first-token logits are compared with the jnp path's on the
   same weights.

Four chips: OLMo-1B's sharded train step on a (data=2, model=2) mesh for 3
steps against the single-device loss of the same parameters and batch.

Phase results go to stdout one JSON line each; seconds printed there are
smoke timings (compilation included), not metrics. The last line is
``{"ok": true, "device": {...}}`` only when every phase passed. Without a
TPU, or outside a checkout of the repository, the script exits non-zero
and prints no result. It starts from an empty result store and an empty
tuned registry under ``.smoke_out/`` and reads no other generated file.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / ".smoke_out"

# bench-dim GEMM: max |kernel - oracle| over max |oracle|, bf16 in/out
GEMM_REL_TOL = 2e-2
# served logits: max |pallas - jnp| over max |jnp| per request
LOGIT_REL_TOL = 5e-2


def _fail(msg: str, code: int = 2):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(code)


def _emit(rec: dict):
    print(json.dumps(rec), flush=True)


class Phases:
    """Runs each phase, records its line, and remembers any failure."""

    def __init__(self):
        self.failed = []

    def run(self, name: str, fn, *args):
        t0 = time.perf_counter()
        try:
            rec = fn(*args)
            ok = bool(rec.pop("ok"))
        except Exception as e:  # noqa: BLE001 — reported, then exit non-zero
            traceback.print_exc()
            rec, ok = {"error": f"{type(e).__name__}: {e}"[:2000]}, False
        _emit({"phase": name, "ok": ok, **rec,
               "smoke_seconds": round(time.perf_counter() - t0, 3)})
        if not ok:
            self.failed.append(name)
        return rec if ok else None


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------

def _verify_counts(v) -> dict:
    """Correctness outcomes, and the fusion groups actually executed on the
    device (the rest were replayed from the verification memo)."""
    return {"candidates_passed_correctness": v.exec_passed,
            "runtime_errors": v.exec_errors,
            "kernel_group_executions": v.group_misses - v.shared_group_hits}


def _verify_ok(counts: dict) -> bool:
    return (counts["candidates_passed_correctness"] > 0
            and counts["runtime_errors"] == 0)


def phase_forge_gemm(forge, state):
    from repro.aibench.runner import KernelRunner
    from repro.aibench.spec import load_specs
    spec = next(s for s in load_specs() if s.name == "gemm_bias_gelu")
    job = KernelRunner(forge=forge).make_job(spec)
    report = forge.optimize(job)
    res = report.result.result
    state["gemm_job"], state["gemm_result"] = job, res
    counts = _verify_counts(report.verify)
    groups = [(g.impl, g.config.to_dict() if g.config else None)
              for g in res.bench_program.schedule.groups]
    return {"ok": _verify_ok(counts), **counts,
            "accepted_stages": [r.stage for r in res.stage_records
                                if r.improved],
            "bench_groups": groups}


def phase_forge_olmo_sites(forge):
    from repro.configs.base import get_config
    from repro.launch.kernel_opt import optimize_arch_kernels
    before = _verify_counts(forge.verify_stats)
    sites = optimize_arch_kernels(get_config("olmo-1b"), forge=forge)
    counts = {k: v - before[k]
              for k, v in _verify_counts(forge.verify_stats).items()}
    tuned = json.loads((OUT / "tuned_kernels.json").read_text())
    return {"ok": _verify_ok(counts)
            and len(tuned.get("matmul_fused", {})) > 0,
            **counts, "sites": sorted(sites), "tuned_matmul_signatures":
                sorted(tuned.get("matmul_fused", {}))}


def phase_bench_gemm(state):
    import jax
    import numpy as np
    from repro.core.executor import run_program
    from repro.core.pipeline import prepare_oracle
    job, res = state["gemm_job"], state["gemm_result"]
    with jax.default_matmul_precision("highest"):
        inputs, params, oracle = prepare_oracle(job.bench_program.graph)
    got = run_program(res.bench_program, inputs, params)
    rec = {"dims": dict(zip("MNK", (4096, 4096, 1024))), "outputs": []}
    ok = len(got) == len(oracle)
    for (name, want), g in zip(oracle.items(), got.values()):
        want, g = np.asarray(want), np.asarray(g)
        ok &= g.shape == want.shape and bool(np.isfinite(g).all())
        diff = float(np.max(np.abs(g - want)))
        scale = float(np.max(np.abs(want)))
        ok &= diff <= GEMM_REL_TOL * scale
        rec["outputs"].append({"name": name, "shape": list(g.shape),
                               "max_abs_diff": diff, "max_abs_oracle": scale,
                               "rel_tol": GEMM_REL_TOL})
    rec["pallas_groups"] = sum(g.impl.startswith("pallas")
                               for g in res.bench_program.schedule.groups)
    return {"ok": ok and rec["pallas_groups"] > 0, **rec}


def phase_serve_olmo():
    import jax
    import numpy as np
    from repro.configs.base import get_config
    from repro.launch.serve import kv_len, make_params, serve
    cfg = get_config("olmo-1b")
    requests, prompt_len, max_new, slots = 8, 128, 32, 4
    params = make_params(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    done = serve(cfg, params, requests=requests, prompt_len=prompt_len,
                 max_new=max_new, slots=slots, use_pallas=True)
    ref = serve(cfg, params, requests=requests, prompt_len=prompt_len,
                max_new=1, slots=slots, use_pallas=False)
    diffs, same_first = [], 0
    for r, q in zip(sorted(done, key=lambda r: r.rid),
                    sorted(ref, key=lambda r: r.rid)):
        d = float(np.max(np.abs(r.prompt_logits - q.prompt_logits)))
        diffs.append(d / float(np.max(np.abs(q.prompt_logits))))
        same_first += r.generated[0] == q.generated[0]
    served = sum(len(r.generated) for r in done)
    ok = (len(done) == requests and served == requests * max_new
          and all(np.isfinite(r.prompt_logits).all() for r in done)
          and max(diffs) <= LOGIT_REL_TOL)
    return {"ok": ok, "arch": cfg.arch, "reduced": False,
            "num_layers": cfg.num_layers, "d_model": cfg.d_model,
            "vocab": cfg.vocab, "params": int(n_params),
            "param_dtype": str(params["embed"].dtype),
            "requests": requests, "slots": slots, "prompt_len": prompt_len,
            "kv_len": kv_len(prompt_len, max_new),
            "tokens_served": served,
            "max_rel_logit_diff_vs_jnp": max(diffs),
            "rel_tol": LOGIT_REL_TOL,
            "first_token_agreement": f"{same_first}/{requests}"}


def run_one_chip(phases: Phases):
    from repro.core.config import ForgeConfig
    from repro.forge import Forge
    state = {}
    config = ForgeConfig(execution_backend="thread", workers=1,
                         cache_path=str(OUT / "forge_store.json"))
    with Forge(config) as forge:
        if phases.run("forge_gemm", phase_forge_gemm, forge, state):
            phases.run("bench_gemm", phase_bench_gemm, state)
        phases.run("forge_olmo_sites", phase_forge_olmo_sites, forge)
    phases.run("serve_olmo", phase_serve_olmo)


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------

def phase_sharded_train(n_devices: int, seq_len: int, batch: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.model import RuntimeFlags, init_params, lm_loss
    from repro.optim import adamw
    from repro.sharding import rules
    from repro.train.train_step import TrainConfig, make_train_step

    cfg = get_config("olmo-1b")
    flags = RuntimeFlags()
    tcfg = TrainConfig()
    key = jax.random.PRNGKey(0)
    init = lambda k: init_params(cfg, k, jnp.float32)   # noqa: E731
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq_len), 0,
                                cfg.vocab)
    host_batch = {"tokens": tokens, "labels": tokens}

    mesh = make_mesh((2, n_devices // 2), ("data", "model"))
    abstract = jax.eval_shape(init, key)
    shp = rules.shard_params(abstract, mesh)
    opt_sh = adamw.OptState(m=shp, v=shp, step=NamedSharding(mesh, P()))
    params = jax.jit(init, out_shardings=shp)(key)
    opt = jax.jit(lambda p: adamw.init(tcfg.optimizer, p),
                  out_shardings=opt_sh)(params)
    batch_sh = rules.shard_batch(jax.eval_shape(lambda: host_batch), mesh)
    data = jax.device_put(host_batch, batch_sh)
    step = jax.jit(make_train_step(cfg, flags, tcfg), donate_argnums=(0, 1))
    losses = []
    with mesh:
        for _ in range(3):
            params, opt, metrics = step(params, opt, data)
            losses.append(float(metrics["loss"]))
    devices = mesh.devices.reshape(-1)
    state_bytes = {str(d.id): sum(
        s.data.nbytes for leaf in jax.tree.leaves((params, opt))
        for s in leaf.addressable_shards if s.device == d) for d in devices}
    memory = {str(d.id): {k: (d.memory_stats() or {}).get(k)
                          for k in ("bytes_in_use", "peak_bytes_in_use")}
              for d in devices}
    del params, opt, data

    # the same parameters and batch, whole on one device
    one = jax.devices()[0]
    ref_params = jax.jit(init, out_shardings=jax.sharding.SingleDeviceSharding(
        one))(key)
    ref_loss = float(jax.jit(lambda p, b: lm_loss(cfg, p, b, flags))(
        ref_params, jax.device_put(host_batch, one)))
    del ref_params
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    spread = list(state_bytes.values())
    ok = (all(np.isfinite(losses)) and rel <= 1e-3
          and min(spread) > 0.5 * max(spread))
    return {"ok": ok, "arch": cfg.arch, "mesh": {"data": 2,
                                                  "model": n_devices // 2},
            "batch": batch, "seq_len": seq_len, "step_losses": losses,
            "single_device_loss": ref_loss, "rel_diff_step1": rel,
            "state_bytes_per_device": state_bytes,
            "memory_per_device": memory}


# ----------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no checkout of the repository around {ROOT} "
              f"(src/repro is missing)")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"no TPU found: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this smoke runs only on a TPU")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"JAX sees {len(devices)}")

    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir()
    # an empty tuned registry of this run's own, never the git-ignored one;
    # set before repro.kernels.ops is imported, which reads it
    os.environ["REPRO_TUNED_KERNELS"] = str(OUT / "tuned_kernels.json")
    from repro.hw.specs import spec_for_device
    from repro.kernels.platform import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache
    _emit({"phase": "setup", "platform": dev.platform,
           "device_kind": dev.device_kind, "device_count": len(devices),
           "spec": spec_for_device(dev).name,
           "pallas_interpret": resolve_interpret(),
           "compile_cache": enable_compile_cache()})

    phases = Phases()
    if args.chips == 4:
        phases.run("sharded_train_4chip", phase_sharded_train, 4, 1024, 8)
    else:
        run_one_chip(phases)
    if phases.failed:
        _fail(f"failed phases: {phases.failed}", code=1)
    _emit({"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": args.chips}})


if __name__ == "__main__":
    main()
