"""Mamba-2 SSD chunked-scan Pallas kernel (state-space duality).

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the sequence is
processed in chunks; within a chunk the recurrence is expressed as a masked
"attention-like" matmul (MXU-friendly), and the chunk-to-chunk state [P, N]
is carried in VMEM scratch across grid steps (persistent-accumulator pattern,
chunk dim marked arbitrary).

Flattened shapes (ops wrapper handles [B, L, H, ...] -> [B*H, L, ...]):
  x  [BH, L, P]   per-head inputs
  dt [BH, L]      positive step sizes
  a  [BH, 1]      negative per-head decay rate
  b  [BH, L, N]   input projection
  c  [BH, L, N]   output projection
Returns y [BH, L, P], final_state [BH, P, N].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, *,
             chunk: int = 128,
             interpret: Optional[bool] = None):
    bh, l, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0, f"seq len {l} must be divisible by chunk {chunk}"
    nchunks = l // chunk

    def kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_ref, state_ref):
        ci = pl.program_id(1)

        @pl.when(ci == 0)
        def _():
            state_ref[...] = jnp.zeros_like(state_ref)

        xq = x_ref[0].astype(jnp.float32)          # [Q, P]
        dtq = dt_ref[0].astype(jnp.float32)        # [Q]
        av = a_ref[0, 0].astype(jnp.float32)       # scalar
        bq = b_ref[0].astype(jnp.float32)          # [Q, N]
        cq = c_ref[0].astype(jnp.float32)          # [Q, N]

        aq = dtq * av                              # [Q], <= 0
        cums = jnp.cumsum(aq)                      # [Q]

        # inter-chunk: contribution of the carried state
        y_inter = jax.lax.dot_general(
            cq, state_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.exp(cums)[:, None]  # [Q, P]

        # intra-chunk: masked decay-weighted "attention"
        scores = jax.lax.dot_general(cq, bq, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)  # [Q, Q]
        li = cums[:, None] - cums[None, :]
        ii = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        decay = jnp.where(ii >= jj, jnp.exp(li), 0.0)
        w = scores * decay * dtq[None, :]
        y_intra = jax.lax.dot_general(w, xq, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

        y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)

        # state update: S' = exp(cums_Q) S + sum_j exp(cums_Q - cums_j) dt_j x_j b_j^T
        total = cums[-1]
        wgt = jnp.exp(total - cums) * dtq          # [Q]
        ds = jax.lax.dot_general(xq * wgt[:, None], bq, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [P, N]
        state_ref[...] = state_ref[...] * jnp.exp(total) + ds

        @pl.when(ci == nchunks - 1)
        def _():
            s_ref[0] = state_ref[...].astype(s_ref.dtype)

    y, s = pl.pallas_call(
        kernel,
        grid=(bh, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, chunk), lambda i, ci: (i, ci)),
            pl.BlockSpec((1, 1), lambda i, ci: (i, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ci: (i, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, p, n), lambda i, ci: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, p), x.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, dt, a, b, c)
    return y, s
