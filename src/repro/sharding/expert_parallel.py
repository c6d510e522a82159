"""Expert parallelism (EP): shard the expert dim over a mesh axis with
explicit all-to-all dispatch, via shard_map.

The assigned MoE archs (8 / 40 experts) do not divide the 16-wide production
``model`` axis, so the production mesh uses TP-within-expert (DESIGN.md §4);
this module provides the real EP path for divisible topologies and is
exercised on fake-device test meshes (tests/test_distribution.py).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L


def apply_moe_ep(cfg: ModelConfig, p: Dict, x: jnp.ndarray, mesh: Mesh,
                 axis: str = "expert", capacity_factor: float = 2.0):
    """EP MoE: tokens all-to-all to their experts' shards and back.

    p["wi"/"wg"/"wo"]: [E, ...] with E % mesh.shape[axis] == 0; x: [B, S, D]
    replicated along ``axis`` (DP axes may shard B outside).
    """
    nshard = mesh.shape[axis]
    e = cfg.moe.num_experts
    assert e % nshard == 0, (e, nshard)
    e_local = e // nshard
    b, s, d = x.shape
    t = b * s

    def shard_fn(x_l, wi, wg, wo, router):
        xt = x_l.reshape(-1, d)
        flat_e, slot, keep, gates, capacity = L.moe_route(
            cfg, {"router": router}, xt, capacity_factor)
        keep_f = keep.astype(xt.dtype)[:, None]
        xr = jnp.repeat(xt, cfg.moe.top_k, axis=0) * keep_f
        # dispatch buffer laid out [E, C, D] then all-to-all over the E dim
        buf = jnp.zeros((e, capacity, d), xt.dtype).at[flat_e, slot].add(xr)
        # exchange: every shard keeps its local experts' slices from everyone
        buf = buf.reshape(nshard, e_local, capacity, d)
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                 tiled=False)
        # buf now: [e_local, nshard, capacity, d] token slices for my experts
        buf = buf.reshape(e_local, nshard * capacity, d)
        hi = jnp.einsum("ecd,edf->ecf", buf, wi)
        hg = jnp.einsum("ecd,edf->ecf", buf, wg)
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(hi) * hg, wo)
        # return to sender
        out = out.reshape(e_local, nshard, capacity, d)
        out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                                 tiled=False)
        out = out.reshape(e, capacity, d)
        gathered = out[flat_e, slot] * keep_f
        y = (gathered.reshape(t, cfg.moe.top_k, d)
             * gates.astype(out.dtype)[..., None]).sum(axis=1)
        return y.reshape(b, s, d)

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(x, p["wi"], p["wg"], p["wo"], p["router"])
