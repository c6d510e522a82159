"""Distribution substrate tests on fake devices (subprocess-isolated where a
different device count is needed; jax locks the count at first init)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_param_spec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import param_spec

    class FakeMesh:
        shape = {"data": 4, "model": 4}
    m = FakeMesh()
    # column-parallel + fsdp on the free dim
    assert param_spec(("layers", "attn", "wq"), (8, 512, 512), m) == \
        P("data", None, "model")
    # row-parallel
    assert param_spec(("layers", "mlp", "wo"), (8, 512, 256), m) == \
        P("data", "model", None)
    # divisibility fallback: odd vocab shards d_model instead
    assert param_spec(("embed",), (51865, 768), m) == P(None, "model")
    assert param_spec(("embed",), (64000, 768), m) == P("model", "data")
    # norms replicated
    assert param_spec(("ln1", "scale"), (512,), m) == P()
    # moe experts: F over model, fsdp on first dividing dim
    spec = param_spec(("layers", "moe", "wi"), (8, 8, 512, 1024), m)
    assert spec == P("data", None, None, "model")


def test_cache_spec_batch1_unsharded_dp():
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import cache_spec

    class FakeMesh:
        shape = {"data": 4, "model": 4}
    m = FakeMesh()
    spec = cache_spec(("k",), (26, 1, 2048, 1, 256), m, kv_heads=1)
    assert spec[1] is None  # batch=1 cannot shard over dp
    spec = cache_spec(("k",), (28, 8, 4096, 4, 128), m, kv_heads=4)
    assert spec == P(None, ("data",), None, "model", None)


def test_grad_compression_int8_ef():
    """Cross-pod int8 EF reduction ~= f32 mean; error feedback shrinks bias
    across steps."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.train.grad_compress import cross_pod_mean, compression_ratio
        mesh = make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)}
        err = jax.tree.map(jnp.zeros_like, g)
        with mesh:
            red, err2 = cross_pod_mean(g, err, mesh)
        # replicated input -> mean == input (within int8 quantization)
        q = np.abs(np.asarray(red["w"]) - np.asarray(g["w"])).max()
        scale = np.abs(np.asarray(g["w"])).max() / 127
        assert q <= scale * 1.01, (q, scale)
        # error feedback captured the quantization residual
        assert np.abs(np.asarray(err2["w"])).max() <= scale * 0.51
        assert compression_ratio(g) > 3.9
        print("OK")
    """), devices=4)
    assert "OK" in out


def test_expert_parallel_matches_dense():
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.launch.mesh import make_mesh
        from repro.models.layers import apply_moe, init_moe
        from repro.sharding.expert_parallel import apply_moe_ep
        cfg = get_config("grok-1-314b").reduced()  # 4 experts
        mesh = make_mesh((4,), ("expert",))
        p = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
        dense = apply_moe(cfg, p, x, capacity_factor=8.0)
        with mesh:
            ep = apply_moe_ep(cfg, p, x, mesh, axis="expert",
                              capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """), devices=4)
    assert "OK" in out


def test_pipeline_parallel_matches_reference():
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.train.pipeline_parallel import (init_mlp_pipeline,
            mlp_stage_fn, pipeline_forward, reference_forward)
        mesh = make_mesh((4,), ("pipe",))
        params = init_mlp_pipeline(jax.random.PRNGKey(0), 4, 16, 32)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 16))  # 8 microbatches
        fn = pipeline_forward(mesh, mlp_stage_fn, 4, 8)
        with mesh:
            got = fn(params, x)
        want = reference_forward(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """), devices=4)
    assert "OK" in out


def test_sharded_train_step_runs_and_matches_single():
    """A small sharded train step on a (2,2) mesh matches the 1-device run."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.launch.mesh import make_mesh
        from repro.models.model import RuntimeFlags, init_params
        from repro.optim import adamw
        from repro.sharding import rules
        from repro.train.train_step import TrainConfig, make_train_step
        cfg = get_config("olmo-1b").reduced()
        flags = RuntimeFlags(remat=False, chunked_attention=False)
        params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        opt = adamw.init(adamw.AdamWConfig(), params)
        tk = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
        batch = {"tokens": tk, "labels": tk}
        step = make_train_step(cfg, flags, TrainConfig())
        p0, o0, m0 = jax.jit(step)(params, opt, batch)   # single device

        mesh = make_mesh((2, 2), ("data", "model"))
        shp = rules.shard_params(params, mesh)
        params_s = jax.device_put(params, shp)
        opt_s = adamw.OptState(
            m=jax.device_put(opt.m, rules.shard_params(opt.m, mesh)),
            v=jax.device_put(opt.v, rules.shard_params(opt.v, mesh)),
            step=opt.step)
        batch_s = jax.device_put(batch, rules.shard_batch(
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()},
            mesh))
        with mesh:
            p1, o1, m1 = jax.jit(step)(params_s, opt_s, batch_s)
        np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                                   rtol=1e-4)
        l0 = jax.tree.leaves(p0)
        l1 = jax.tree.leaves(p1)
        for a, b in zip(l0, l1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)
        print("OK")
    """), devices=4)
    assert "OK" in out


def test_elastic_checkpoint_across_device_counts(tmp_path):
    """Save sharded on 4 devices, restore+train on 2 (elastic restart)."""
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs.base import get_config
        from repro.launch.mesh import make_mesh
        from repro.models.model import init_params
        from repro.sharding import rules
        cfg = get_config("olmo-1b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        mesh = make_mesh((DEV, 1), ("data", "model"))
        params = jax.device_put(params, rules.shard_params(params, mesh))
        mgr = CheckpointManager(r"{tmp_path}")
        STEP
    """)
    save = code.replace("DEV", "4").replace(
        "STEP", "mgr.save(1, params); print('SAVED')")
    out = _run(save, devices=4)
    assert "SAVED" in out
    load = code.replace("DEV", "2").replace("STEP", textwrap.dedent("""
        restored, step = mgr.restore(
            params, shardings=rules.shard_params(params, mesh))
        import numpy as np
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('RESTORED', step)
    """))
    out = _run(load, devices=2)
    assert "RESTORED 1" in out
