"""Flash-attention Pallas kernels (forward).

* :func:`flash_attention` — online-softmax tiled kernel: grid over
  (batch*heads, q-tiles, kv-tiles) with kv innermost/arbitrary; running
  (m, l, acc) statistics live in VMEM scratch across kv steps (the
  persistent-row-reduction pattern). Supports causal masking, local
  (sliding-window) masking, and GQA via a head-mapping index.

* :func:`attention_unoptimized` — the "original" kernel the pipeline starts
  from (paper Fig. 14 blue bars): per q-tile it loads the FULL K/V into VMEM
  and materializes the full score row — correct, VMEM-hungry, unpipelined.

Shapes: q [B, H, Sq, D], k/v [B, Hkv, Skv, D]; Hkv divides H.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30


def _cdiv(a, b):
    return -(-a // b)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = False,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_kv: int = 128,
                    acc_dtype=jnp.float32,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    q_per_kv = h // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    qt, kt = _cdiv(sq, block_q), _cdiv(skv, block_kv)
    # align query/key positions at the sequence end (prefill & decode agree)
    off = skv - sq

    def kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        qi, kj = pl.program_id(1), pl.program_id(2)

        @pl.when(kj == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        qv = q_ref[0].astype(acc_dtype)          # [bq, d]
        kv_ = k_ref[0].astype(acc_dtype)         # [bkv, d]
        s = jax.lax.dot_general(qv, kv_, (((1,), (1,)), ((), ())),
                                preferred_element_type=acc_dtype) * scale

        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + off
        kpos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < skv  # ragged tail
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(acc_dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)
        m_ref[...] = m_new

        @pl.when(kj == kt - 1)
        def _():
            l = l_ref[...]
            l = jnp.where(l == 0.0, 1.0, l)       # fully-masked rows -> 0 output
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kv_head(bh):  # map flat q-head index -> flat kv-head index
        return (bh // h) * hkv + (bh % h) // q_per_kv

    out = pl.pallas_call(
        kernel,
        grid=(b * h, qt, kt),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, kj: (kv_head(bh), kj, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, kj: (kv_head(bh), kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), acc_dtype),
                        pltpu.VMEM((block_q, 1), acc_dtype),
                        pltpu.VMEM((block_q, d), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)


def attention_unoptimized(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                          causal: bool = False,
                          scale: Optional[float] = None,
                          block_q: int = 128,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """The KernelFalcon-style starting point: full-KV per q-tile, full score
    materialization, single-pass softmax. O(Skv) VMEM per program."""
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    q_per_kv = h // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    block_q = min(block_q, sq)
    qt = _cdiv(sq, block_q)
    off = skv - sq

    def kernel(q_ref, k_ref, v_ref, o_ref):
        qi = pl.program_id(1)
        qv = q_ref[0].astype(jnp.float32)
        kv_ = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(qv, kv_, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + off
            kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_ref[0] = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kv_head(bh):
        return (bh // h) * hkv + (bh % h) // q_per_kv

    out = pl.pallas_call(
        kernel,
        grid=(b * h, qt),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, skv, d), lambda bh, qi: (kv_head(bh), 0, 0)),
            pl.BlockSpec((1, skv, d), lambda bh, qi: (kv_head(bh), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
