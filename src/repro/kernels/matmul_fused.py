"""Fused matmul Pallas kernel (the workhorse of the L2 suite).

Two implementations, matching the pipeline's before/after axis:

* :func:`matmul_fused_naive` — "manual pointer arithmetic": whole-array refs,
  flat output grid, explicit ``ref[pl.ds(..), pl.ds(..)]`` tile indexing,
  serial K loop in the body. Mosaic gets no BlockSpecs, so nothing is pipelined. This
  is the Triton-without-``make_block_ptr`` analogue the block-pointer stage
  modernizes away.

* :func:`matmul_fused` — BlockSpec-tiled: swizzled flat grid over output
  tiles (GROUP_M traversal), K as an innermost ``arbitrary`` grid dim with a
  persistent f32 VMEM accumulator, fused epilogue chain applied in-register,
  optional terminal row-reduction that never materializes the [M, N] result.

Config knobs map 1:1 to :class:`repro.ir.schedule.PallasConfig`.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import (EpilogueOp, apply_epilogue, reduce_combine,
                                    reduce_init, reduce_tile)
from repro.kernels.platform import resolve_interpret

# scoped-VMEM room above the naive kernel's resident arrays (Mosaic's own
# scratch, the register-spilled accumulator)
_VMEM_HEADROOM = 16 * 2**20


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _normalize_operand(name: str, arr: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    """Normalize epilogue operands to 2D [1|M, 1|N] for block mapping."""
    if arr.ndim == 0:
        return arr.reshape(1, 1)
    if arr.ndim == 1:
        if arr.shape[0] == n:
            return arr.reshape(1, n)
        if arr.shape[0] == m:
            return arr.reshape(m, 1)
        raise ValueError(f"operand {name}: 1D shape {arr.shape} matches neither M nor N")
    if arr.ndim == 2:
        return arr
    raise ValueError(f"operand {name}: rank {arr.ndim} unsupported")


def _operand_spec(arr: jnp.ndarray, bm: int, bn: int, m_of, n_of):
    """BlockSpec for a normalized [1|M, 1|N] operand."""
    om = arr.shape[0] != 1
    on = arr.shape[1] != 1
    bshape = (bm if om else 1, bn if on else 1)

    def idx(*grid_ids):
        return (m_of(*grid_ids) if om else 0, n_of(*grid_ids) if on else 0)

    return pl.BlockSpec(bshape, idx)


def _swizzle(p, mt: int, nt: int, group_m: int):
    """GROUP_M grid traversal (Triton matmul-tutorial swizzle, TPU edition)."""
    if group_m <= 1:
        return p // nt, p % nt
    group_size = group_m * nt
    gid = p // group_size
    first_m = gid * group_m
    gsz = jnp.minimum(mt - first_m, group_m)
    m = first_m + (p % group_size) % gsz
    n = (p % group_size) // gsz
    return m, n


# ======================================================================
# BlockSpec (modernized) implementation
# ======================================================================

def matmul_fused(a: jnp.ndarray, b: jnp.ndarray, *,
                 block_m: int = 128, block_n: int = 128, block_k: int = 128,
                 group_m: int = 1, num_stages: int = 2,
                 epilogue: Optional[List[EpilogueOp]] = None,
                 operands: Optional[Dict[str, jnp.ndarray]] = None,
                 reduction: Optional[str] = None,
                 acc_dtype=jnp.float32,
                 out_dtype=None,
                 dimension_semantics: Tuple[str, ...] = ("parallel", "arbitrary"),
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """C = epilogue(A @ B) [optionally reduced over N]. A: [M,K], B: [K,N]."""
    epilogue = epilogue or []
    operands = operands or {}
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    mt, nt, kt = _cdiv(m, block_m), _cdiv(n, block_n), _cdiv(k, block_k)

    op_names = sorted({e.operand for e in epilogue if e.operand is not None})
    norm_ops = {s: _normalize_operand(s, operands[s], m, n) for s in op_names}

    interpret = resolve_interpret(interpret)
    if reduction is None:
        return _matmul_epilogue_swizzled(
            a, b, norm_ops, op_names, epilogue, m, n, k,
            block_m, block_n, block_k, group_m, mt, nt, kt,
            acc_dtype, out_dtype, num_stages, dimension_semantics, interpret)
    return _matmul_reduce(
        a, b, norm_ops, op_names, epilogue, reduction, m, n, k,
        block_m, block_n, block_k, mt, nt, kt, acc_dtype, out_dtype, interpret)


def _matmul_epilogue_swizzled(a, b, norm_ops, op_names, epilogue, m, n, k,
                              bm, bn, bk, group_m, mt, nt, kt,
                              acc_dtype, out_dtype, num_stages,
                              dimension_semantics, interpret):
    m_of = lambda p, kk: _swizzle(p, mt, nt, group_m)[0]
    n_of = lambda p, kk: _swizzle(p, mt, nt, group_m)[1]

    k_ragged = k % bk != 0

    def kernel(a_ref, b_ref, *rest):
        *op_refs, o_ref, acc_ref = rest
        kk = pl.program_id(1)

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a_tile, b_tile = a_ref[...], b_ref[...]
        if k_ragged:
            # partial contraction blocks must be explicitly zero-masked: the
            # pipeline pads loads, and padded *contraction* columns would
            # pollute real outputs (padded M/N rows are store-masked instead)
            kpos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            a_tile = jnp.where(kpos < k, a_tile, 0)
            b_tile = jnp.where(kpos.reshape(bk, 1) < k, b_tile, 0)
        acc_ref[...] += jnp.dot(a_tile, b_tile,
                                preferred_element_type=acc_dtype)

        @pl.when(kk == kt - 1)
        def _():
            tile_ops = {s: r[...] for s, r in zip(op_names, op_refs)}
            tile = apply_epilogue(acc_ref[...], epilogue, tile_ops)
            o_ref[...] = tile.astype(o_ref.dtype)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda p, kk: (m_of(p, kk), kk)),
        pl.BlockSpec((bk, bn), lambda p, kk: (kk, n_of(p, kk))),
    ]
    in_specs += [_operand_spec(norm_ops[s], bm, bn, m_of, n_of) for s in op_names]

    return pl.pallas_call(
        kernel,
        grid=(mt * nt, kt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda p, kk: (m_of(p, kk), n_of(p, kk))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=tuple(dimension_semantics)[:2] or ("parallel", "arbitrary")),
        interpret=interpret,
        name="matmul_fused_epilogue",
    )(a, b, *[norm_ops[s] for s in op_names])


def _matmul_reduce(a, b, norm_ops, op_names, epilogue, reduction, m, n, k,
                   bm, bn, bk, mt, nt, kt, acc_dtype, out_dtype, interpret):
    """Row-reduction epilogue: grid (mt, nt, kt); the [M, N] product is never
    materialized — per-n-tile partials fold into a [bm, 1] scratch."""
    m_of = lambda i, j, kk: i
    n_of = lambda i, j, kk: j

    k_ragged = k % bk != 0

    def kernel(a_ref, b_ref, *rest):
        *op_refs, o_ref, acc_ref, red_ref = rest
        j, kk = pl.program_id(1), pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a_tile, b_tile = a_ref[...], b_ref[...]
        if k_ragged:
            kpos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            a_tile = jnp.where(kpos < k, a_tile, 0)
            b_tile = jnp.where(kpos.reshape(bk, 1) < k, b_tile, 0)
        acc_ref[...] += jnp.dot(a_tile, b_tile,
                                preferred_element_type=acc_dtype)

        @pl.when(kk == kt - 1)
        def _():
            tile_ops = {s: r[...] for s, r in zip(op_names, op_refs)}
            tile = apply_epilogue(acc_ref[...], epilogue, tile_ops)
            # mask ragged N so padded columns don't pollute the reduction
            ncol = j * bn + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
            neutral = jnp.asarray(reduce_init(reduction), tile.dtype)
            tile = jnp.where(ncol < n, tile, neutral)
            part = reduce_tile(tile, reduction, axis=-1, keepdims=True)

            @pl.when(j == 0)
            def _():
                red_ref[...] = part

            @pl.when(j > 0)
            def _():
                red_ref[...] = reduce_combine(red_ref[...], part, reduction)

            @pl.when(j == nt - 1)
            def _():
                res = red_ref[...]
                if reduction == "mean":
                    res = res / n
                o_ref[...] = res.astype(o_ref.dtype)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    in_specs += [_operand_spec(norm_ops[s], bm, bn, m_of, n_of) for s in op_names]

    out = pl.pallas_call(
        kernel,
        grid=(mt, nt, kt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype),
                        pltpu.VMEM((bm, 1), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="matmul_fused_reduce",
    )(a, b, *[norm_ops[s] for s in op_names])
    return out[:, 0]


# ======================================================================
# Naive (manual pointer arithmetic) implementation
# ======================================================================

def matmul_fused_naive(a: jnp.ndarray, b: jnp.ndarray, *,
                       block_m: int = 128, block_n: int = 128, block_k: int = 128,
                       epilogue: Optional[List[EpilogueOp]] = None,
                       operands: Optional[Dict[str, jnp.ndarray]] = None,
                       reduction: Optional[str] = None,
                       out_dtype=None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """The 'unoptimized input kernel': flat grid, manual tile indexing
    (``ref[pl.ds(..), pl.ds(..)]``) over whole arrays held in VMEM, f32
    accumulation in registers, no masking, no pipelining. Requires divisible
    shapes (the missing_boundary_check issue, on purpose)."""
    epilogue = epilogue or []
    operands = operands or {}
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"naive kernel has no boundary checks: shape ({m},{n},{k}) not "
            f"divisible by blocks ({block_m},{block_n},{block_k})")
    mt, nt, kt = m // block_m, n // block_n, k // block_k

    op_names = sorted({e.operand for e in epilogue if e.operand is not None})
    norm_ops = {s: _normalize_operand(s, operands[s], m, n) for s in op_names}

    def kernel(a_ref, b_ref, *rest):
        op_refs = rest[:len(op_names)]
        o_ref = rest[len(op_names)]
        p = pl.program_id(0)
        mi, ni = p // nt, p % nt
        rows = pl.ds(pl.multiple_of(mi * block_m, block_m), block_m)
        cols = pl.ds(pl.multiple_of(ni * block_n, block_n), block_n)

        def k_step(acc, k0):
            ks = pl.ds(k0, block_k)
            return acc + jnp.dot(a_ref[rows, ks], b_ref[ks, cols],
                                 preferred_element_type=jnp.float32)

        acc = jnp.zeros((block_m, block_n), jnp.float32)
        if block_k % 128 == 0:
            acc = jax.lax.fori_loop(0, kt, lambda kk, acc: k_step(
                acc, pl.multiple_of(kk * block_k, block_k)), acc)
        else:
            # Mosaic proves a dynamic lane offset aligned only when it is a
            # multiple of 128; unaligned K tiles (the GPU-default 32) take
            # static offsets instead
            for kk in range(kt):
                acc = k_step(acc, kk * block_k)
        tile_ops = {}
        for s, r in zip(op_names, op_refs):
            arr = norm_ops[s]
            rsel = rows if arr.shape[0] != 1 else pl.ds(0, 1)
            csel = cols if arr.shape[1] != 1 else pl.ds(0, 1)
            tile_ops[s] = r[rsel, csel]
        tile = apply_epilogue(acc, epilogue, tile_ops)
        if reduction is None:
            o_ref[rows, cols] = tile.astype(o_ref.dtype)
        else:
            part = reduce_tile(tile, reduction, axis=-1, keepdims=True)
            if reduction == "mean":
                part = part / n
            # every n-tile accumulates into the same column: serialized, racy
            # unless the grid is sequential — which on TPU it is (no swizzle).
            prev = o_ref[rows, pl.ds(0, 1)]
            init = jnp.asarray(reduce_init(reduction), jnp.float32)
            prev = jnp.where(ni == 0, jnp.full_like(prev, init.astype(prev.dtype)), prev)
            comb = reduce_combine(prev.astype(jnp.float32), part, reduction)
            o_ref[rows, pl.ds(0, 1)] = comb.astype(o_ref.dtype)

    out_shape = (m, n) if reduction is None else (m, 1)
    args = [a, b] + [norm_ops[s] for s in op_names]
    # whole arrays live in VMEM for the kernel's lifetime (no blocking, so
    # no double buffering); the scoped limit is raised to hold them
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    resident = (sum(x.size * x.dtype.itemsize for x in args)
                + out_shape[0] * out_shape[1] * jnp.dtype(out_dtype).itemsize)
    out = pl.pallas_call(
        kernel,
        grid=(mt * nt,),
        in_specs=[whole] * len(args),
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=resident + _VMEM_HEADROOM),
        interpret=resolve_interpret(interpret),
        name="matmul_fused_naive",
    )(*args)
    if reduction is not None:
        # mean already rescaled in-kernel
        return out[:, 0]
    return out
