"""Fused single-pass RMSNorm Pallas kernel.

x: [R, d] (leading dims flattened by the ops wrapper), w: [d].
One read, one write; f32 math regardless of io dtype.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _cdiv(a, b):
    return -(-a // b)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-6,
            block_rows: int = 256,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    r, d = x.shape
    block_rows = min(block_rows, r)
    rt = _cdiv(r, block_rows)

    def kernel(x_ref, w_ref, o_ref):
        xv = x_ref[...].astype(jnp.float32)
        var = jnp.mean(jnp.square(xv), axis=-1, keepdims=True)
        y = xv * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(rt,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
        name="rmsnorm",
    )(x, w.reshape(1, d))
