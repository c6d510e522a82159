"""Grouped SwiGLU over the experts a chip holds (Pallas).

Tokens arrive grouped by expert: ``x[e, :counts[e]]`` are the rows routed
to held expert ``e``, and the rows past the count are not used. The grid
walks the experts and, for each, the intermediate width in tiles of
``block_f``: the three matrices of each expert with rows are read once per
call, and its rows stay in VMEM across its tiles. An expert with no rows
reads nothing: its blocks point at the ones already in VMEM (``counts`` and
the last expert with rows ride in SMEM as scalar prefetch), so the call
reads the weights of the experts its tokens chose and, where expert 0 has
no rows, one tile of expert 0 that the first grid step fetches. Within a
tile only the row blocks that hold routed tokens are computed. The whole
group of an expert sits in VMEM, so the group is a decode step's tokens,
not a training batch's.

x: [E, C, D]; counts: [E] int32; wi, wg: [E, D, F]; wo: [E, F, D].
Returns [E, C, D]: ``(silu(x wi) * (x wg)) wo`` for rows below the count,
zero past it.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def moe_experts(x: jnp.ndarray, counts: jnp.ndarray, wi: jnp.ndarray,
                wg: jnp.ndarray, wo: jnp.ndarray, *, block_f: int = 256,
                block_rows: int = 64,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    e, c, d = x.shape
    f = wi.shape[-1]
    bf = block_f if f % block_f == 0 else f
    br = block_rows if c % block_rows == 0 else c
    nf = f // bf
    counts = counts.reshape(e).astype(jnp.int32)
    # the expert whose blocks are in VMEM when expert e's turn comes: e
    # itself if it has rows, else the last one before it that had (its
    # last tile), so that an empty expert's blocks equal the previous
    # step's and the pipeline copies nothing
    resident = jax.lax.cummax(jnp.where(counts > 0, jnp.arange(e), 0))

    def tile(ei, fj, cnt, res):
        return res[ei], jnp.where(cnt[ei] > 0, fj, nf - 1)

    def x_map(ei, fj, cnt, res):
        return res[ei], 0, 0

    def w_in_map(ei, fj, cnt, res):
        ex, ft = tile(ei, fj, cnt, res)
        return ex, 0, ft

    def w_out_map(ei, fj, cnt, res):
        ex, ft = tile(ei, fj, cnt, res)
        return ex, ft, 0

    def kernel(cnt_ref, res_ref, x_ref, wi_ref, wg_ref, wo_ref, o_ref,
               acc_ref):
        ei, fj = pl.program_id(0), pl.program_id(1)
        n = cnt_ref[ei]

        @pl.when(fj == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for r in range(c // br):
            @pl.when(r * br < n)
            def _():
                xr = x_ref[0, r * br:(r + 1) * br, :]
                h = jnp.dot(xr, wi_ref[0], preferred_element_type=jnp.float32)
                g = jnp.dot(xr, wg_ref[0], preferred_element_type=jnp.float32)
                a = (h * jax.nn.sigmoid(h) * g).astype(x_ref.dtype)
                acc_ref[r * br:(r + 1) * br, :] += jnp.dot(
                    a, wo_ref[0], preferred_element_type=jnp.float32)

        @pl.when(fj == nf - 1)
        def _():
            rows = jax.lax.broadcasted_iota(jnp.int32, (c, d), 0)
            o_ref[0] = jnp.where(rows < n, acc_ref[...], 0.0).astype(
                o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e, nf),
        in_specs=[
            pl.BlockSpec((1, c, d), x_map),
            pl.BlockSpec((1, d, bf), w_in_map),
            pl.BlockSpec((1, d, bf), w_in_map),
            pl.BlockSpec((1, bf, d), w_out_map),
        ],
        out_specs=pl.BlockSpec((1, c, d), lambda ei, fj, cnt, res: (ei, 0, 0)),
        scratch_shapes=[pltpu.VMEM((c, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="moe_experts",
    )(counts, resident, x, wi, wg, wo)
