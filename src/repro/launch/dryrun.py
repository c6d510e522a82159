import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
# a compile study on host devices, never a chip run: pinning the CPU keeps
# every dry-run process (dryrun_all fans out one per cell) off the TPU
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh without allocating a single weight.

MUST set XLA_FLAGS and JAX_PLATFORMS before ANY other import (jax locks the
device count and platform on first init) — hence the module-top assignments.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k --mesh single --out results/qwen3_train_single.json

One cell per process by default (compilation caches/arenas are per-process;
the orchestrator ``dryrun_all.py`` fans out subprocesses and merges JSON).
"""

import argparse
import json
import pathlib
import time
import traceback
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, get_config
from repro.configs.shapes import SHAPES, applicability
from repro.launch.input_specs import (abstract_cache, abstract_opt_state,
                                      abstract_params, input_specs)
from repro.launch.mesh import make_production_mesh
from repro.models.model import RuntimeFlags, decode_step, prefill
from repro.roofline.analyze import collective_bytes
from repro.train.train_step import TrainConfig, make_train_step


def count_params(abstract_p) -> float:
    return float(sum(x.size for x in jax.tree.leaves(abstract_p)))


def active_params(cfg: ModelConfig, abstract_p) -> float:
    """N_active: MoE experts (all of them, or the share held) count at
    top_k/E weight."""
    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract_p)[0]:
        keys = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
        is_expert = cfg.moe is not None and "moe" in keys \
            and keys[-1] in ("wi", "wg", "wo")
        if is_expert:
            total += leaf.size * cfg.moe.top_k / cfg.moe.num_experts
        else:
            total += leaf.size
    return float(total)


def model_flops(cfg: ModelConfig, shape, n_params: float,
                n_active: float) -> float:
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n = n_active if cfg.moe else n_params
    if shape.kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             donate: bool = True) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicability(cfg, shape_name)
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind}
    if not ok:
        rec.update(status="skip", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    rec["n_devices"] = mesh.devices.size
    variant = os.environ.get("REPRO_VARIANT", "")
    vtok = {t.split("=")[0]: (t.split("=")[1] if "=" in t else True)
            for t in variant.split(",") if t}
    rec["variant"] = variant
    flags = RuntimeFlags(
        use_pallas=False, chunked_attention=True,
        remat=(shape.kind == "train") and not vtok.get("no_remat"),
        loss_chunks=int(vtok.get("loss_chunks", 8)))

    t0 = time.time()
    params = abstract_params(cfg, mesh, jnp.bfloat16)
    n_params = count_params(params)
    n_active = active_params(cfg, params)
    rec["n_params"] = n_params
    rec["n_active_params"] = n_active
    rec["model_flops"] = model_flops(cfg, shape, n_params, n_active)

    # activation-heavy train cells use gradient accumulation (standard
    # practice; microbatch counts recorded in the cell output)
    microbatches = {"llava-next-34b": 4, "granite-moe-3b-a800m": 1,
                    "qwen3-8b": 2, "grok-1-314b": 2,
                    "recurrentgemma-2b": 8, "mamba2-780m": 2}.get(arch, 1) \
        if shape.kind == "train" else 1
    if "mb" in vtok:
        microbatches = int(vtok["mb"])
    rec["microbatches"] = microbatches

    with mesh:
        if shape.kind == "train":
            opt = abstract_opt_state(cfg, params, mesh)
            batch = input_specs(cfg, shape, mesh)
            step_fn = make_train_step(cfg, flags,
                                      TrainConfig(microbatches=microbatches))
            jitted = jax.jit(step_fn,
                             donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(params, opt, batch)
        elif shape.kind == "prefill":
            batch = input_specs(cfg, shape, mesh)
            # the produced cache must come out sharded like the decode cells
            # consume it; without out_shardings SPMD may replicate it
            cache_sh = jax.tree.map(lambda a: a.sharding,
                                    abstract_cache(cfg, shape, mesh))
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.sharding.rules import dp_prefix_for
            logits_sh = NamedSharding(
                mesh, P(dp_prefix_for(mesh, shape.global_batch),
                        "model" if cfg.vocab % mesh.shape["model"] == 0
                        else None))
            jitted = jax.jit(lambda p, b: prefill(cfg, p, b, flags),
                             out_shardings=(logits_sh, cache_sh))
            lowered = jitted.lower(params, batch)
        else:  # decode
            kv_dtype = jnp.int8 if vtok.get("kvq") == "int8" else jnp.bfloat16
            cache = abstract_cache(cfg, shape, mesh, dtype=kv_dtype)
            batch = input_specs(cfg, shape, mesh)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.sharding.rules import dp_prefix_for
            cache_sh = jax.tree.map(lambda a: a.sharding, cache)
            logits_sh = NamedSharding(
                mesh, P(dp_prefix_for(mesh, shape.global_batch),
                        "model" if cfg.vocab % mesh.shape["model"] == 0
                        else None))
            jitted = jax.jit(
                lambda p, c, t, i: decode_step(cfg, p, c, t, i, flags),
                donate_argnums=(1,) if donate else (),
                out_shardings=(logits_sh, cache_sh))
            lowered = jitted.lower(params, cache, batch["tokens"], pos)
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    text = compiled.as_text()
    # CPU-backend artifact (documented in EXPERIMENTS.md §Dry-run): the CPU
    # backend legalizes bf16 compute to f32 and hoists the loop-invariant
    # FSDP weight all-gathers out of the layer scan, materializing f32
    # stacked-weight buffers (and their backward mirrors) that do not exist
    # on the TPU target (bf16, gathered per layer inside the loop). Quantify
    # them so the projected-TPU peak is reportable alongside the raw one.
    import re as _re
    artifact = 0
    for m in _re.finditer(r"=\s*f32\[(\d+(?:,\d+)*)\]\S*\s+all-gather", text):
        dims = [int(d) for d in m.group(1).split(",")]
        if dims and dims[0] == cfg.num_layers and len(dims) >= 3:
            n = 1
            for d in dims:
                n *= d
            artifact += 4 * n
    if shape.kind == "train":
        artifact *= 2  # backward holds the mirrored f32 stacked grads
    peak = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_per_device": peak,
        "cpu_backend_artifact_bytes": int(artifact),
        "peak_projected_tpu": int(peak - artifact),
    }
    rec["fits_hbm"] = rec["memory"]["peak_projected_tpu"] <= 16 * 2**30
    rec["fits_hbm_raw_cpu"] = peak <= 16 * 2**30
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # jax 0.4.x: one dict per computation
        ca = ca[0] if ca else {}
    rec["cost_raw"] = {"flops": float(ca.get("flops", 0.0)),
                       "bytes": float(ca.get("bytes accessed", 0.0)),
                       "transcendentals": float(ca.get("transcendentals", 0.0))}
    # the backend's cost_analysis counts while-loop bodies ONCE; the walker
    # multiplies by known_trip_count (scan-over-layers, microbatches, chunked
    # attention). flops/collectives exact per-chip; bytes scaled by the same
    # loop multiplier (documented approximation).
    from repro.roofline.hlo_walker import walk
    w = walk(text)
    ratio = (w.flops / rec["cost_raw"]["flops"]
             if rec["cost_raw"]["flops"] > 0 else 1.0)
    rec["cost"] = {
        "flops": float(w.flops),                       # per-chip, trip-exact
        "bytes": float(w.hbm_bytes),                   # per-chip, trip-exact
        "loop_multiplier": float(ratio),
    }
    rec["collectives"] = dict(w.coll_by_kind, total=float(w.coll_bytes))
    rec["collectives_unrolled_raw"] = collective_bytes(text)
    rec["hlo_lines"] = text.count("\n")
    rec["status"] = "ok"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multipod"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    try:
        rec = run_cell(args.arch, args.shape, args.mesh)
    except Exception as e:  # noqa: BLE001 — a failing cell is a reportable bug
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    js = json.dumps(rec, indent=2)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(js)
    print(js if rec.get("status") != "ok" else json.dumps(
        {k: rec[k] for k in ("arch", "shape", "mesh", "status", "compile_s",
                             "fits_hbm")}, indent=None))
    if rec.get("status") == "ok":
        print("memory_analysis:", rec["memory"])
        print("cost_analysis:", rec["cost"])
        print("collectives:", rec["collectives"])


if __name__ == "__main__":
    main()
