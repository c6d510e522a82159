"""Fused elementwise-chain Pallas kernel.

Executes an epilogue chain (k pointwise ops) in one pass: one HBM read per
input, one write — the fusion stage's product for chains with no contraction.
x: [R, C] (leading dims flattened by the ops wrapper).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import EpilogueOp, apply_epilogue
from repro.kernels.matmul_fused import _normalize_operand, _operand_spec
from repro.kernels.platform import resolve_interpret


def _cdiv(a, b):
    return -(-a // b)


def elementwise_chain(x: jnp.ndarray, epilogue: List[EpilogueOp], *,
                      operands: Optional[Dict[str, jnp.ndarray]] = None,
                      block_rows: int = 256,
                      out_dtype=None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    operands = operands or {}
    r, c = x.shape
    out_dtype = out_dtype or x.dtype
    block_rows = min(block_rows, r)
    rt = _cdiv(r, block_rows)

    op_names = sorted({e.operand for e in epilogue if e.operand is not None})
    norm_ops = {s: _normalize_operand(s, operands[s], r, c) for s in op_names}

    def kernel(x_ref, *rest):
        op_refs, o_ref = rest[:len(op_names)], rest[len(op_names)]
        tile_ops = {s: ref[...] for s, ref in zip(op_names, op_refs)}
        y = apply_epilogue(x_ref[...].astype(jnp.float32), epilogue, tile_ops)
        o_ref[...] = y.astype(o_ref.dtype)

    m_of = lambda i: i
    n_of = lambda i: 0
    in_specs = [pl.BlockSpec((block_rows, c), lambda i: (i, 0))]
    in_specs += [_operand_spec(norm_ops[s], block_rows, c, m_of, n_of)
                 for s in op_names]

    return pl.pallas_call(
        kernel,
        grid=(rt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
        name="elementwise_chain",
    )(x, *[norm_ops[s] for s in op_names])
