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
    # the step sizes ride in as a row [1, Q] and as a column [Q, 1] of each
    # chunk (Mosaic tiles the last two dims of a block), the decay rates in
    # SMEM as scalar prefetch; prefix sums are products with a triangle
    dt32 = dt.astype(jnp.float32)
    dt_row, dt_col = dt32.reshape(bh, 1, l), dt32.reshape(bh, l, 1)
    a_s = a.reshape(bh).astype(jnp.float32)

    def kernel(a_ref, x_ref, dtr_ref, dtc_ref, b_ref, c_ref, y_ref, s_ref,
               state_ref):
        i, ci = pl.program_id(0), pl.program_id(1)

        @pl.when(ci == 0)
        def _():
            state_ref[...] = jnp.zeros_like(state_ref)

        xq = x_ref[0].astype(jnp.float32)          # [Q, P]
        av = a_ref[i]                              # scalar, < 0
        dtr, dtc = dtr_ref[0], dtc_ref[0]          # [1, Q], [Q, 1]
        bq = b_ref[0].astype(jnp.float32)          # [Q, N]
        cq = c_ref[0].astype(jnp.float32)          # [Q, N]

        ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        lower = (ii >= jj).astype(jnp.float32)
        cums_c = jax.lax.dot_general(lower, dtc * av, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        cums_r = jax.lax.dot_general(dtr * av, lower, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        total = jnp.sum(dtr * av, axis=1, keepdims=True)         # [1, 1]

        # inter-chunk: contribution of the carried state
        y_inter = jax.lax.dot_general(
            cq, state_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.exp(cums_c)  # [Q, P]

        # intra-chunk: masked decay-weighted "attention"
        scores = jax.lax.dot_general(cq, bq, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)  # [Q, Q]
        decay = jnp.where(ii >= jj, jnp.exp(cums_c - cums_r), 0.0)
        w = scores * decay * dtr
        y_intra = jax.lax.dot_general(w, xq, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

        y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)

        # state update: S' = exp(cums_Q) S + sum_j exp(cums_Q - cums_j) dt_j x_j b_j^T
        wgt = jnp.exp(total - cums_c) * dtc        # [Q, 1]
        ds = jax.lax.dot_general(xq * wgt, bq, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [P, N]
        state_ref[...] = state_ref[...] * jnp.exp(total) + ds

        @pl.when(ci == nchunks - 1)
        def _():
            s_ref[0] = state_ref[...].astype(s_ref.dtype)

    y, s = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nchunks),
            in_specs=[
                pl.BlockSpec((1, chunk, p), lambda i, ci, a_: (i, ci, 0)),
                pl.BlockSpec((1, 1, chunk), lambda i, ci, a_: (i, 0, ci)),
                pl.BlockSpec((1, chunk, 1), lambda i, ci, a_: (i, ci, 0)),
                pl.BlockSpec((1, chunk, n), lambda i, ci, a_: (i, ci, 0)),
                pl.BlockSpec((1, chunk, n), lambda i, ci, a_: (i, ci, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, p), lambda i, ci, a_: (i, ci, 0)),
                pl.BlockSpec((1, p, n), lambda i, ci, a_: (i, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, p), x.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a_s, x, dt_row, dt_col, b, c)
    return y, s
