"""Split-KV decode attention (flash-decoding) Pallas kernel.

Decode shapes (q_len=1, huge KV) leave the q-tile grid of a prefill kernel
with no parallelism; the decode kernel instead parallelizes over KV blocks and
merges partial softmax statistics — the persistent-row-reduction pattern
applied along KV. Supports GQA (q heads grouped over kv heads) and per-batch
valid lengths (paged-cache-style ragged KV).

q: [B, H, D]; k/v: [B, Hkv, S, D]; lengths: [B] or None.
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


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     lengths: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None,
                     block_kv: int = 512,
                     acc_dtype=jnp.float32,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    b, h, d = q.shape
    _, hkv, s, _ = k.shape
    assert h % hkv == 0
    q_per_kv = h // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    block_kv = min(block_kv, s)
    kt = _cdiv(s, block_kv)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    # per-row valid lengths ride in SMEM as scalar prefetch: a (1, 1) VMEM
    # block of a (B, 1) array is neither the full array nor tile-aligned
    lengths = lengths.reshape(b).astype(jnp.int32)

    # group q heads that share a kv head into one tile: [B*Hkv, q_per_kv, D]
    qf = q.reshape(b, hkv, q_per_kv, d).reshape(b * hkv, q_per_kv, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)

    def kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        kj = pl.program_id(1)

        @pl.when(kj == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        qv = q_ref[0].astype(acc_dtype)                  # [qpk, d]
        kv_ = k_ref[0].astype(acc_dtype)                 # [bkv, d]
        st = jax.lax.dot_general(qv, kv_, (((1,), (1,)), ((), ())),
                                 preferred_element_type=acc_dtype) * scale
        kpos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        valid = kpos < len_ref[pl.program_id(0) // hkv]
        st = jnp.where(valid, st, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(acc_dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)
        m_ref[...] = m_new

        @pl.when(kj == kt - 1)
        def _():
            l = l_ref[...]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, kt),
        in_specs=[
            pl.BlockSpec((1, q_per_kv, d), lambda bh, kj, lens: (bh, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, lens: (bh, kj, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, kj, lens: (bh, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_per_kv, d),
                               lambda bh, kj, lens: (bh, 0, 0)),
        scratch_shapes=[pltpu.VMEM((q_per_kv, 1), acc_dtype),
                        pltpu.VMEM((q_per_kv, 1), acc_dtype),
                        pltpu.VMEM((q_per_kv, d), acc_dtype)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, q_per_kv, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="decode_attention",
    )(lengths, qf, kf, vf)
    return out.reshape(b, h, d)
