"""End-to-end behaviour: training reduces loss; serving generates; kernel-opt
integration writes the tuned registry; one real dry-run cell compiles."""

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.model import RuntimeFlags, init_params
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import TrainConfig
from repro.train.trainer import Trainer

TCFG = TrainConfig(optimizer=AdamWConfig(lr=3e-3, warmup_steps=3,
                                         total_steps=60))

REPO = pathlib.Path(__file__).resolve().parents[1]
FLAGS = RuntimeFlags(remat=False, chunked_attention=False)


def test_training_reduces_loss():
    cfg = get_config("olmo-1b").reduced()
    t = Trainer(cfg, seq_len=64, global_batch=4, flags=FLAGS, seed=0,
                tcfg=TCFG)
    hist = t.train(40)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first * 0.95, (first, last)


def test_moe_training_reduces_loss():
    cfg = get_config("granite-moe-3b-a800m").reduced()
    t = Trainer(cfg, seq_len=48, global_batch=4, flags=FLAGS, seed=0,
                tcfg=TCFG)
    hist = t.train(30)
    assert hist[-1]["loss"] < hist[0]["loss"]
    assert all(np.isfinite(h["grad_norm"]) for h in hist)


def test_serve_engine_generates():
    from repro.serve.engine import Request, ServeEngine
    cfg = get_config("qwen3-8b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    engine = ServeEngine(cfg, params, max_len=32, slots=2, flags=FLAGS)
    rng = np.random.default_rng(0)
    for i in range(3):
        engine.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab, 8),
                              max_new_tokens=6))
    done = engine.run()
    assert len(done) == 3
    assert all(len(r.generated) == 6 for r in done)
    # greedy decode is deterministic: same prompt -> same continuation
    e2 = ServeEngine(cfg, params, max_len=32, slots=2, flags=FLAGS)
    e2.submit(Request(rid=0, prompt=done[0].prompt.copy(), max_new_tokens=6))
    again = e2.run()
    assert again[0].generated == done[0].generated


def test_serve_launcher_bf16_logits_match_jnp_decode():
    """launch.serve at bf16: the Pallas decode path's first-token logits
    agree with the jnp path on the same weights, and the KV cache is
    tile-aligned."""
    from repro.launch.serve import kv_len, make_params, serve
    assert kv_len(128, 32) == 256 and kv_len(8, 4) == 128
    cfg = get_config("olmo-1b").reduced()
    params = make_params(cfg)
    kw = dict(requests=2, prompt_len=8, max_new=3, slots=2)
    pallas = serve(cfg, params, use_pallas=True, **kw)
    plain = serve(cfg, params, use_pallas=False, **kw)
    for a, b in zip(pallas, plain):
        assert a.prompt_logits.shape == (cfg.vocab,)
        np.testing.assert_allclose(a.prompt_logits, b.prompt_logits,
                                   atol=0.05 * np.abs(b.prompt_logits).max())
        assert len(a.generated) == 3


def test_compile_cache_location(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    prior = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.CHECKOUT_CACHE.parent == REPO
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)


def test_kernel_opt_writes_registry(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNED_KERNELS", str(tmp_path / "kernels.json"))
    import importlib
    import repro.kernels.ops as ops
    importlib.reload(ops)
    from repro.launch.kernel_opt import optimize_arch_kernels
    cfg = get_config("olmo-1b").reduced()
    results = optimize_arch_kernels(cfg, seq_len=512, batch=2, max_sites=2)
    assert any(v.get("speedup_vs_naive", 0) > 1 for v in results.values()
               if isinstance(v, dict) and "speedup_vs_naive" in v)
    data = json.loads((tmp_path / "kernels.json").read_text())
    assert "matmul_fused" in data and "flash_attention" in data
    monkeypatch.delenv("REPRO_TUNED_KERNELS")
    importlib.reload(ops)


@pytest.mark.slow
def test_one_real_dryrun_cell():
    """A real 512-device multi-pod compile in a subprocess (the cheapest cell)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = REPO / "results" / "test_cell.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-780m",
         "--shape", "long_500k", "--mesh", "multipod", "--out", str(out)],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 512
    assert rec["fits_hbm"]
    assert rec["collectives"]["total"] > 0
