"""Functional model layers (pure JAX, pytree params).

Conventions:
  * params are nested dicts of jnp arrays; init fns are PRNG-keyed and work
    under ``jax.eval_shape`` (the dry-run never materializes weights).
  * activations flow as [B, S, D]; attention heads grouped as
    [B, S, Hkv, G, Dh] (G = query heads per KV head) so GQA never has to
    materialize repeated KV.
  * every layer takes ``use_pallas`` — True routes the hot spots through the
    Pallas kernels (interpret mode on CPU); False uses the jnp path that the
    multi-pod dry-run lowers.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def shard_hint(x: jnp.ndarray, *entries) -> jnp.ndarray:
    """Best-effort sharding constraint: applies only when an ambient mesh is
    active (the launcher's ``with mesh:``), drops axis names the mesh lacks,
    and guards divisibility — so model code stays mesh-agnostic and tests on
    one device are unaffected. Entries may be None, an axis name, or a tuple
    of axis names."""
    try:
        import os
        if os.environ.get("REPRO_NO_SP"):  # perf-iteration variant (§Perf)
            return x
        from jax._src import mesh as mesh_lib
        env = mesh_lib.thread_resources.env.physical_mesh
        if env.empty:
            return x
        names = set(env.axis_names)
        clean = []
        for dim, e in enumerate(entries):
            if e is None:
                clean.append(None)
                continue
            axes = e if isinstance(e, tuple) else (e,)
            axes = tuple(a for a in axes if a in names)
            size = 1
            for a in axes:
                size *= env.shape[a]
            if not axes or size <= 1 or x.shape[dim] % size != 0:
                clean.append(None)
            else:
                clean.append(axes if len(axes) > 1 else axes[0])
        if all(c is None for c in clean):
            return x
        from jax.sharding import PartitionSpec
        return jax.lax.with_sharding_constraint(x, PartitionSpec(*clean))
    except Exception:  # noqa: BLE001 — hints must never break execution
        return x


# ======================================================================
# norms
# ======================================================================

def init_norm(cfg: ModelConfig, dtype) -> Dict:
    if cfg.non_parametric_ln:
        return {}
    return {"scale": jnp.ones((cfg.d_model,), dtype)}


def apply_norm(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
               use_pallas: bool = False) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    eps = cfg.resolved_norm_eps
    if cfg.norm == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    else:
        if use_pallas and x.ndim >= 2 and not cfg.non_parametric_ln:
            from repro.kernels.ops import rms_norm
            return rms_norm(x, p["scale"], eps=eps)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps)
    if not cfg.non_parametric_ln and "scale" in p:
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ======================================================================
# rotary embeddings
# ======================================================================

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, Dh]; positions: [S]. Trailing-dim broadcasting aligns the
    [S, Dh/2] angle table against any leading batch/head dims."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs  # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ======================================================================
# attention (GQA, qk-norm, bias, local window, cross)
# ======================================================================

def init_attention(cfg: ModelConfig, key, dtype, cross: bool = False) -> Dict:
    d, dh = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, h * dh), dtype),
        "wk": _dense_init(ks[1], (d, hkv * dh), dtype),
        "wv": _dense_init(ks[2], (d, hkv * dh), dtype),
        "wo": _dense_init(ks[3], (h * dh, d), dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def _qk_normalize(q, scale, eps):
    var = jnp.mean(jnp.square(q.astype(jnp.float32)), axis=-1, keepdims=True)
    return (q.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(q.dtype)


def _project_qkv(cfg: ModelConfig, p: Dict, x, kv_x, positions, kv_positions,
                 use_rope: bool):
    b, sq, d = x.shape
    skv = kv_x.shape[1]
    dh = cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.kv_heads
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, sq, h, dh)
    k = k.reshape(b, skv, hkv, dh)
    v = v.reshape(b, skv, hkv, dh)
    if cfg.qk_norm:
        q = _qk_normalize(q, p["q_norm"], cfg.resolved_norm_eps)
        k = _qk_normalize(k, p["k_norm"], cfg.resolved_norm_eps)
    if use_rope:
        q = rope(q.swapaxes(1, 2), positions, cfg.rope_theta).swapaxes(1, 2)
        k = rope(k.swapaxes(1, 2), kv_positions, cfg.rope_theta).swapaxes(1, 2)
    return q, k, v


def grouped_attention(q, k, v, *, causal: bool, window: Optional[int],
                      q_offset: int = 0, kv_chunk: int = 1024,
                      q_chunk: int = 2048, chunked: bool = True) -> jnp.ndarray:
    import os as _os
    if _os.environ.get("REPRO_ATTN_CHUNK"):  # §Perf iteration variant
        kv_chunk = q_chunk = int(_os.environ["REPRO_ATTN_CHUNK"])
    """Memory-efficient grouped attention.

    q: [B, Sq, H, Dh]; k, v: [B, Skv, Hkv, Dh]. Returns [B, Sq, H, Dh].
    ``chunked`` runs the Flash-Attention recurrence at the XLA level: an
    outer serial map over q blocks, an inner scan over KV blocks with running
    max/sum — peak score memory is O(q_chunk x kv_chunk), never S x S.
    Required for the 32k/500k shapes, and the jnp mirror of the Pallas flash
    kernel.
    """
    b, sq, h, dh = q.shape
    _, skv, hkv, _ = k.shape
    g = h // hkv
    scale = 1.0 / math.sqrt(dh)
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    def mask_for(qpos, kpos):
        m = None
        if causal:
            m = kpos[None, :] <= qpos[:, None]
        if window is not None:
            w = kpos[None, :] > (qpos[:, None] - window)
            m = w if m is None else (m & w)
        return m

    if not chunked or skv <= kv_chunk:
        qg = q.reshape(b, sq, hkv, g, dh).astype(jnp.float32) * scale
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k32)
        m = mask_for(jnp.arange(sq) + q_offset, jnp.arange(skv))
        if m is not None:
            s = jnp.where(m[None, None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", pr, v32)
        return out.reshape(b, sq, h, dh).astype(q.dtype)

    nkv = -(-skv // kv_chunk)
    kpad = nkv * kv_chunk - skv
    if kpad:
        k32 = jnp.pad(k32, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v32 = jnp.pad(v32, ((0, 0), (0, kpad), (0, 0), (0, 0)))
    kc = k32.reshape(b, nkv, kv_chunk, hkv, dh).transpose(1, 0, 2, 3, 4)
    vc = v32.reshape(b, nkv, kv_chunk, hkv, dh).transpose(1, 0, 2, 3, 4)

    q_chunk = min(q_chunk, sq)
    nq = -(-sq // q_chunk)
    qpad = nq * q_chunk - sq
    qp = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0))) if qpad else q
    qb = qp.reshape(b, nq, q_chunk, hkv, g, dh).transpose(1, 0, 2, 3, 4, 5)

    def one_q_block(args):
        qi, qblk = args
        qg = qblk.astype(jnp.float32) * scale          # [b, qc, hkv, g, dh]
        qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        @partial(jax.checkpoint, prevent_cse=False)
        def step(carry, inp):
            # checkpointed: backward recomputes the probability tile instead
            # of saving O(S^2) residuals (the flash-backward memory property)
            m_run, l_run, acc = carry
            idx, kb, vb = inp
            kpos = idx * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb)
            msk = (kpos < skv)[None, :]
            mm = mask_for(qpos, kpos)
            if mm is not None:
                msk = msk & mm
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_run, m_cur)
            pr = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(pr, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", pr, vb)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, hkv, g, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, dh), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            step, (m0, l0, a0), (jnp.arange(nkv), kc, vc))
        l_f = jnp.where(l_f == 0.0, 1.0, l_f)
        return (acc / l_f[..., None]).transpose(0, 3, 1, 2, 4)  # [b,qc,hkv,g,dh]

    if nq == 1:
        out = one_q_block((jnp.asarray(0), qb[0]))
    else:
        outs = jax.lax.map(one_q_block, (jnp.arange(nq), qb))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * q_chunk, hkv, g, dh)
        out = out[:, :sq] if qpad else out
        return out.reshape(b, sq, h, dh).astype(q.dtype)
    out = out[:, :sq] if qpad else out
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def apply_attention(cfg: ModelConfig, p: Dict, x, *,
                    kv_x=None, positions=None, kv_positions=None,
                    causal=True, window=None, use_rope=True,
                    use_pallas: bool = False, chunked: bool = True):
    """Self- (kv_x=None) or cross-attention over full sequences."""
    b, sq, d = x.shape
    kv_x = x if kv_x is None else kv_x
    skv = kv_x.shape[1]
    positions = positions if positions is not None else jnp.arange(sq)
    kv_positions = kv_positions if kv_positions is not None else jnp.arange(skv)
    q, k, v = _project_qkv(cfg, p, x, kv_x, positions, kv_positions, use_rope)
    if use_pallas:
        from repro.kernels.ops import attention as pallas_attention
        out = pallas_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), causal=causal,
                               window=window)
        out = out.transpose(0, 2, 1, 3)
    else:
        out = grouped_attention(q, k, v, causal=causal, window=window,
                                q_offset=skv - sq, chunked=chunked)
    return out.reshape(b, sq, cfg.num_heads * cfg.resolved_head_dim) @ p["wo"]


KV_Q_SCALE = 32.0  # fixed-point scale for int8 KV caches (serving option)


def _kv_quant(x, dtype):
    if dtype == jnp.int8:
        return jnp.clip(jnp.round(x.astype(jnp.float32) * KV_Q_SCALE),
                        -127, 127).astype(jnp.int8)
    return x.astype(dtype)


def _kv_dequant(x):
    if x.dtype == jnp.int8:
        return x.astype(jnp.float32) / KV_Q_SCALE
    return x.astype(jnp.float32)


def decode_attention_step(cfg: ModelConfig, p: Dict, x, cache_k, cache_v,
                          position, *, window=None, use_rope=True,
                          use_pallas: bool = False):
    """One-token decode. x: [B, 1, D]; cache_k/v: [B, Smax, Hkv, Dh] (bf16 or
    int8 fixed-point); position: scalar index of the new token. Returns
    (out, new_k, new_v)."""
    b, _, d = x.shape
    smax = cache_k.shape[1]
    pos_arr = jnp.full((1,), position)
    q, k_new, v_new = _project_qkv(cfg, p, x, x, pos_arr, pos_arr, use_rope)
    k_new = _kv_quant(k_new, cache_k.dtype)
    v_new = _kv_quant(v_new, cache_v.dtype)
    if window is not None and smax == window:
        # rolling window cache: shift left, append at the end
        cache_k = jnp.concatenate([cache_k[:, 1:], k_new], axis=1)
        cache_v = jnp.concatenate([cache_v[:, 1:], v_new], axis=1)
        lengths = jnp.minimum(position + 1, window)
        kpos_last = position
        valid = (jnp.arange(smax) > (smax - 1 - lengths))
        k_eff, v_eff = cache_k, cache_v
        length_mask = valid
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, position, 1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, position, 1)
        k_eff, v_eff = cache_k, cache_v
        length_mask = jnp.arange(smax) <= position
    if use_pallas and window is None:
        from repro.kernels.ops import decode_attn
        qh = q.reshape(b, cfg.num_heads, cfg.resolved_head_dim)
        k_eff = _kv_dequant(k_eff) if k_eff.dtype == jnp.int8 else k_eff
        v_eff = _kv_dequant(v_eff) if v_eff.dtype == jnp.int8 else v_eff
        out = decode_attn(qh, k_eff.transpose(0, 2, 1, 3),
                          v_eff.transpose(0, 2, 1, 3),
                          lengths=jnp.full((b,), position + 1, jnp.int32))
        out = out.reshape(b, 1, -1)
    else:
        h, hkv = cfg.num_heads, cfg.kv_heads
        dh = cfg.resolved_head_dim
        g = h // hkv
        qg = q.reshape(b, hkv, g, dh).astype(jnp.float32) / math.sqrt(dh)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, _kv_dequant(k_eff))
        s = jnp.where(length_mask[None, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", pr, _kv_dequant(v_eff))
        out = out.reshape(b, 1, h * dh).astype(x.dtype)
    return out @ p["wo"], cache_k, cache_v


# ======================================================================
# MLP / MoE
# ======================================================================

def init_mlp(cfg: ModelConfig, key, dtype, d_ff: Optional[int] = None) -> Dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"wi": _dense_init(ks[0], (d, f), dtype),
         "wo": _dense_init(ks[1], (f, d), dtype)}
    if cfg.glu:
        p["wg"] = _dense_init(ks[2], (d, f), dtype)
    return p


def _act(cfg: ModelConfig, x):
    return jax.nn.silu(x) if cfg.activation == "silu" else jax.nn.gelu(x)


def apply_mlp(cfg: ModelConfig, p: Dict, x, use_pallas: bool = False):
    h = _act(cfg, x @ p["wi"])
    if cfg.glu:
        h = h * (x @ p["wg"])
    return h @ p["wo"]


def init_moe(cfg: ModelConfig, key, dtype) -> Dict:
    """The router over every expert, and the weights of the experts held
    (all of them unless ``cfg.moe.held`` says otherwise)."""
    m = cfg.moe
    d, f, e = cfg.d_model, cfg.d_ff, m.num_experts
    held = e if m.held is None else m.held
    ks = jax.random.split(key, 4)
    p = {
        "router": _dense_init(ks[0], (d, e), dtype),
        "wi": _dense_init(ks[1], (held, d, f), dtype),
        "wg": _dense_init(ks[2], (held, d, f), dtype),
        "wo": _dense_init(ks[3], (held, f, d), dtype),
    }
    if m.router == "sigmoid":
        p["expert_bias"] = jnp.zeros((e,), jnp.float32)
    return p


def sigmoid_gates(cfg: ModelConfig, p: Dict, xt: jnp.ndarray):
    """Sigmoid routing of tokens ``xt`` [T, D] over every expert: the top k
    of score + expert bias [T, K], and their scores normalised to sum 1
    (float32 gates [T, K])."""
    scores = jax.nn.sigmoid((xt @ p["router"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + p["expert_bias"], cfg.moe.top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)


def moe_route(cfg: ModelConfig, p: Dict, xt: jnp.ndarray,
              capacity_factor: float = 1.25):
    """Top-k routing with static expert capacity. Returns
    (flat_expert [T*K], slot [T*K], keep [T*K], gates [T, K], capacity)."""
    t, d = xt.shape
    e, top_k = cfg.moe.num_experts, cfg.moe.top_k
    logits = (xt @ p["router"]).astype(jnp.float32)        # [T, E]
    gate_vals, idx = jax.lax.top_k(logits, top_k)          # [T, K]
    gates = jax.nn.softmax(gate_vals, axis=-1)
    capacity = max(1, int(capacity_factor * t * top_k / e))
    flat_e = idx.reshape(-1)                               # [T*K]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)    # [T*K, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1              # position per expert
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = slot < capacity
    return flat_e, jnp.where(keep, slot, capacity - 1), keep, gates, capacity


def apply_moe(cfg: ModelConfig, p: Dict, x, use_pallas: bool = False,
              capacity_factor: float = 1.25):
    """GShard-style *grouped* capacity dispatch: tokens are routed
    independently within each batch group (group = one batch row), so the
    cumsum/scatter/gather machinery is vmapped over a dim that is sharded
    over DP — no cross-shard prefix sums, no replicated dispatch buffers
    under SPMD. Over-capacity tokens drop (standard). The EP all-to-all
    variant lives in sharding/expert_parallel.py. A config that holds a
    share of the experts (``cfg.moe.held``) takes :func:`apply_moe_held`."""
    if cfg.moe.held is not None:
        return apply_moe_held(cfg, p, x, use_pallas)[0]
    b, s, d = x.shape
    e, top_k = cfg.moe.num_experts, cfg.moe.top_k
    tg = s                                        # tokens per group
    capacity = max(1, int(capacity_factor * tg * top_k / e))
    xg = x                                        # [G=b, Tg=s, D]

    def route_group(xt):                          # [Tg, D] local to one group
        logits = (xt @ p["router"]).astype(jnp.float32)
        gate_vals, idx = jax.lax.top_k(logits, top_k)
        gates = jax.nn.softmax(gate_vals, axis=-1)
        flat_e = idx.reshape(-1)                  # [Tg*K]
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = slot < capacity
        slot = jnp.where(keep, slot, capacity - 1)
        keep_f = keep.astype(xt.dtype)[:, None]
        xr = jnp.repeat(xt, top_k, axis=0) * keep_f
        buf = jnp.zeros((e, capacity, d), xt.dtype).at[flat_e, slot].add(xr)
        return buf, flat_e, slot, keep_f, gates

    buf, flat_e, slot, keep_f, gates = jax.vmap(route_group)(xg)
    # pin activation shardings: G over DP, F over model. Without these, the
    # FSDP data-sharding on the weights' contraction dim makes SPMD regather
    # the G dim (21 GB/device hidden tensors on grok) instead of the weights.
    dp = ("pod", "data")
    buf = shard_hint(buf, dp, None, None, None)            # [G, E, C, D]
    hi = jnp.einsum("gecd,edf->gecf", buf, p["wi"])
    hi = shard_hint(hi, dp, None, None, "model")
    hg = jnp.einsum("gecd,edf->gecf", buf, p["wg"])
    hg = shard_hint(hg, dp, None, None, "model")
    hh = _act(cfg, hi) * hg
    out = jnp.einsum("gecf,efd->gecd", hh, p["wo"])        # [G, E, C, D]
    out = shard_hint(out, dp, None, None, None)

    def combine_group(out_g, fe, sl, kf, gt):
        gathered = out_g[fe, sl] * kf                      # [Tg*K, D]
        return (gathered.reshape(tg, top_k, d)
                * gt.astype(out_g.dtype)[..., None]).sum(axis=1)

    y = jax.vmap(combine_group)(out, flat_e, slot, keep_f, gates)
    return y.reshape(b, s, d)


def apply_moe_held(cfg: ModelConfig, p: Dict, x, use_pallas: bool = False):
    """The part of an expert layer that the held experts give: every token
    is routed over all ``num_experts``, and each token-expert pair whose
    expert is held here is computed, none dropped (a token picks an expert
    at most once, so a group of T rows holds any routing). Pairs routed
    elsewhere add nothing here; under expert parallelism the shares of the
    other chips add up to the rest. x: [..., D]. Returns the part, and the
    rows each held expert computed [held] int32."""
    from repro.kernels.ops import experts
    m = cfg.moe
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    t, k = xt.shape[0], m.top_k
    idx, gates = sigmoid_gates(cfg, p, xt)
    local = idx.reshape(-1) - m.first_held                 # [T*K]
    mine = (local >= 0) & (local < m.held)
    group = jnp.where(mine, local, m.held)                 # m.held: not here
    order = jnp.argsort(group, stable=True)                # pairs by group
    counts = jnp.sum(group[:, None] == jnp.arange(m.held)[None, :], axis=0,
                     dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    # row r of expert e's group holds the token of its (starts[e] + r)-th
    # pair in sorted order; rows at or past counts[e] are unused
    pick = jnp.minimum(starts[:, None] + jnp.arange(t)[None, :], t * k - 1)
    out = experts(xt[order[pick] // k], counts, p["wi"], p["wg"], p["wo"],
                  use_pallas=use_pallas)                   # [held, T, D]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    row = rank - starts[jnp.minimum(group, m.held - 1)]
    rows = out[jnp.where(mine, local, 0), jnp.where(mine, row, 0)]
    w = jnp.where(mine, gates.reshape(-1), 0.0).reshape(t, k)
    y = jnp.sum(w[..., None] * rows.reshape(t, k, d).astype(jnp.float32),
                axis=1)
    return y.astype(x.dtype).reshape(x.shape), counts


def moe_aux_loss(cfg: ModelConfig, p: Dict, x) -> jnp.ndarray:
    """Switch-style load-balancing auxiliary loss. Sigmoid routers take
    their scores normalised over the experts as the probabilities."""
    b, s, d = x.shape
    e = cfg.moe.num_experts
    logits = (x.reshape(-1, d) @ p["router"]).astype(jnp.float32)
    if cfg.moe.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        logits = scores + p["expert_bias"]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(logits, cfg.moe.top_k)
    frac = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    return e * jnp.sum(frac * jnp.mean(probs, axis=0))
