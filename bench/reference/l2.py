"""Plain float32 references of the Level-2 GEMM problems.

Each is the problem's mathematics in ``jax.numpy`` at float32 and
``Precision.HIGHEST``, written from the problem's statement and not from the
program's graph. GELU is the tanh form, which is what the problems state.
``quant="fp8"`` rounds every operand to float8_e4m3fn first (one scale per
tensor): the control, the nearest precision below the bfloat16 target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _gelu(x):
    return jax.nn.gelu(x, approximate=True)


PROBLEMS = {
    "gemm_bias_gelu": lambda x, w, bias: _gelu(_mm(x, w) + bias),
    "gemm_swish_tanh_scale": lambda x, w: 2.0 * jnp.tanh(jax.nn.silu(_mm(x, w))),
    "gemm_max_subtract_gelu":
        lambda x, w: _gelu(jnp.max(_mm(x, w), axis=1) - 0.5),
    "gemm_divide_sum": lambda x, w: jnp.sum(0.5 * _mm(x, w), axis=1),
    "gemm_scale_residual": lambda x, resid, w: 0.125 * _mm(x, w) + resid,
    "gemm_branch_duplicate": lambda x, w: 2.0 * _gelu(_mm(x, w)),
    "gemm_mean_scale": lambda x, w: 3.0 * jnp.mean(_mm(x, w), axis=1),
    "gemm_softplus_min":
        lambda x, w: jnp.min(jax.nn.softplus(_mm(x, w)), axis=1),
    "gemm_transpose_transpose": lambda x, w: jax.nn.relu(_mm(x, w)),
    "matmul_t_gelu": lambda x, w: _gelu(_mm(x, w.T)),
    "matmul_min_subtract": lambda x, w: jnp.min(_mm(x, w), axis=1) - 1.0,
    "matmul_t_scale_swish": lambda x, w: jax.nn.silu(0.25 * _mm(x, w.T)),
    "matmul_serial_sum": lambda x, w: jnp.sum(_mm(x, w), axis=1),
    "matmul_materialized_t": lambda x, w: jnp.tanh(_mm(x.T, w)),
    "matmul_dropout_tanh": lambda x, w: jnp.tanh(_mm(x, w)),
    "matmul_double_cast": lambda x, w: _gelu(_mm(x, w)),
}


def _q8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnums=(0, 2))
def _run(name: str, operands: dict, quant):
    ops = {k: v.astype(jnp.float32) for k, v in operands.items()}
    if quant == "fp8":
        ops = {k: _q8(v) for k, v in ops.items()}
    return PROBLEMS[name](**ops)


def output(name: str, operands: dict, quant=None):
    """The problem's output for ``operands`` (name -> array)."""
    with jax.default_matmul_precision("highest"):
        return _run(name, operands, quant)
