"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

The assigned production mesh dedicates its axes to DP x TP (DESIGN.md §5), so
PP is an *optional* topology: the launcher can build a ("pipe", "data") mesh
and stage the layer stack. Implementation: shard_map over ``pipe``; each
stage holds L/P layers; microbatches stream through a lax.scan schedule with
``ppermute`` handoffs (warmup bubbles included — the classic GPipe
fill/drain), loss computed on the last stage and broadcast back.

This module is exercised by tests/test_distribution.py on fake devices; it is
deliberately self-contained (simple MLP blocks) so the schedule logic is
testable without the full model zoo.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(mesh: Mesh, stage_fn: Callable, num_stages: int,
                     num_microbatches: int):
    """Build fn(stage_params, x_microbatches) -> y_microbatches.

    stage_params: pytree with leading [num_stages, ...] dim (sharded on pipe);
    x_microbatches: [num_microbatches, mb, ...] (replicated; stage 0 consumes).
    stage_fn(params_stage, x) -> y applies one stage.
    """
    assert num_microbatches >= num_stages, "need >= stages microbatches"

    def per_stage(params_stage, xs):
        # params_stage: [1, ...] local slice; xs: [M, mb, ...] full stream
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        stage = jax.lax.axis_index("pipe")
        m = xs.shape[0]
        total = m + num_stages - 1
        mb_shape = xs.shape[1:]

        def step(carry, t):
            outputs, prev_out = carry
            # receive from the previous stage (stage 0 reads the stream)
            recv = jax.lax.ppermute(
                prev_out, "pipe",
                [(i, (i + 1) % num_stages) for i in range(num_stages)])
            idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(stage == 0,
                             xs[idx].astype(jnp.float32),
                             recv)
            active = (t - stage >= 0) & (t - stage < m)
            y = stage_fn(params_stage, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage commits its output at slot t - stage
            out_idx = jnp.clip(t - stage, 0, m - 1)
            commit = active & (stage == num_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(commit, y, outputs[out_idx]), out_idx, 0)
            return (outputs, y), None

        outputs0 = jnp.zeros((m,) + mb_shape, jnp.float32)
        prev0 = jnp.zeros(mb_shape, jnp.float32)
        (outputs, _), _ = jax.lax.scan(step, (outputs0, prev0),
                                       jnp.arange(total))
        # only the last stage holds real outputs; broadcast them
        outputs = jax.lax.psum(
            jnp.where(stage == num_stages - 1, outputs, jnp.zeros_like(outputs)),
            "pipe")
        return outputs

    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P("pipe"), P()),
        out_specs=P(),
        check_vma=False)


def mlp_stage_fn(params_stage, x):
    """Reference stage: two-matmul MLP block (used by tests/examples)."""
    h = jnp.tanh(x @ params_stage["w1"])
    return h @ params_stage["w2"]


def init_mlp_pipeline(key, num_stages: int, d: int, dh: int):
    ks = jax.random.split(key, 2)
    return {
        "w1": jax.random.normal(ks[0], (num_stages, d, dh), jnp.float32) / d**0.5,
        "w2": jax.random.normal(ks[1], (num_stages, dh, d), jnp.float32) / dh**0.5,
    }


def reference_forward(params, x_microbatches):
    """Sequential oracle for the pipeline schedule."""
    def apply_all(x):
        for s in range(params["w1"].shape[0]):
            x = mlp_stage_fn(jax.tree.map(lambda a: a[s], params), x)
        return x
    return jax.vmap(apply_all)(x_microbatches.astype(jnp.float32))
