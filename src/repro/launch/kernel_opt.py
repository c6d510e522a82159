"""Forge-pipeline integration for the framework's kernel call-sites
(DESIGN.md §3.1): run the paper's optimization pipeline over the kernel
shapes an architecture actually uses, and persist the winning configs in the
tuned registry that ``kernels/ops.py`` consults.

Call-sites optimized per arch:
  * fused matmul sites: the MLP in/out projections (and MoE expert FFN dims),
    attention qkv/out projections, the logits matmul;
  * flash-attention site: (seq, seq, head_dim) from the shape spec;
  * decode-attention site: KV length from the shape spec.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from repro.configs.base import ModelConfig
from repro.core.engine import KernelJob
from repro.forge import Forge, ForgeConfig
from repro.ir.cost import graph_flops
from repro.ir.graph import GraphBuilder
from repro.ir.schedule import KernelProgram, PallasConfig, eager_schedule
from repro.kernels.ops import REGISTRY, _query, _sig


def matmul_sites(cfg: ModelConfig, seq_len: int, batch: int
                 ) -> List[Tuple[str, int, int, int]]:
    t = batch * seq_len
    d, f = cfg.d_model, cfg.d_ff
    dh = cfg.resolved_head_dim
    sites = []
    if f:
        sites.append(("mlp_in", t, f, d))
        sites.append(("mlp_out", t, d, f))
    if cfg.num_heads:
        sites.append(("attn_qkv", t, cfg.num_heads * dh, d))
        sites.append(("attn_out", t, d, cfg.num_heads * dh))
    sites.append(("logits", t, cfg.vocab, d))
    return sites


def _gemm_program(name: str, m: int, n: int, k: int) -> KernelProgram:
    b = GraphBuilder(name)
    x = b.input((m, k), name="x")
    w = b.param((k, n), name="w")
    mm = b.matmul(x, w, name="mm")
    g = b.done(mm)
    sched = eager_schedule(g)
    for grp in sched.groups:
        grp.impl = "pallas_naive"
        grp.config = PallasConfig(128, 128, 32, num_stages=1)
    return KernelProgram(name, g, sched, original_flops=graph_flops(g))


def optimize_arch_kernels(cfg: ModelConfig, seq_len: int = 4096,
                          batch: int = 8, max_sites: int = 5,
                          workers: int = 1,
                          backend: str = "thread",
                          forge: Forge = None,
                          cache_path=None) -> Dict:
    # submit all call-sites as one batch: identically-shaped sites (e.g. MoE
    # experts sharing dims, or archs revisited across launches with a
    # persistent cache) replay instead of re-optimizing; differently-shaped
    # GEMM sites are family twins, so the first cold site seeds the rest
    # through the store's near-miss transfer path
    owns_forge = forge is None
    forge = forge or Forge(ForgeConfig(
        workers=workers,
        execution_backend=backend,
        cache_path=str(cache_path) if cache_path else None))
    sites = matmul_sites(cfg, seq_len, batch)[:max_sites]
    jobs = []
    for name, m, n, k in sites:
        mc = min(m, 256)
        nc = min(n, 256)
        kc = min(k, 128)
        jobs.append(KernelJob(f"{cfg.arch}:{name}",
                              _gemm_program(name, mc, nc, kc),
                              _gemm_program(name, m, n, k),
                              tags=("gemm",)))
    results = {}
    try:
        batch_results = forge.optimize_batch(jobs)
    finally:
        if owns_forge:
            # a process-backend forge keeps spawned workers warm; don't
            # leak them when the forge was created for this call only
            forge.close()
    for (name, m, n, k), eres in zip(sites, batch_results):
        res = eres.result
        grp = next((g for g in res.bench_program.schedule.groups
                    if g.impl == "pallas_blockspec" and g.config), None)
        if grp is not None:
            c = grp.config
            REGISTRY.put("matmul_fused", _sig(m, n, k, "bfloat16"), {
                "block_m": c.block_m, "block_n": c.block_n,
                "block_k": c.block_k, "group_m": c.group_m,
                "num_stages": c.num_stages})
        results[name] = {"speedup_vs_naive": round(res.speedup, 2),
                         "dims": [m, n, k], "cache_hit": eres.cache_hit,
                         "transfer": eres.transfer,
                         "seed_steps": eres.seed_steps}
    # attention sites straight from the hardware query of the chip the
    # kernels compile for (the pipeline's gpu-specific stage delegates
    # attention tiling to it)
    ap = _query().get_attention_params(seq_len, seq_len, cfg.resolved_head_dim or 128)
    REGISTRY.put("flash_attention",
                 _sig(seq_len, seq_len, cfg.resolved_head_dim or 128, "bfloat16"),
                 {"block_q": ap.block_m, "block_kv": ap.block_n})
    results["flash_attention"] = {"block_q": ap.block_m, "block_kv": ap.block_n}
    return results
