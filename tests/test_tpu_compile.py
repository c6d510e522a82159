"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Each test lowers one kernel at the width the chip runs it and compiles it
for a *described* v5e:2x2 topology: nothing runs, but Mosaic refuses here
what it would refuse on the chip (unaligned slices, VMEM over budget,
unsupported block shapes). The topology is described inside a module-scoped
fixture only, so importing this file never loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.elementwise import elementwise_chain
from repro.kernels.epilogue import EpilogueOp
from repro.kernels.flash_attention import (attention_unoptimized,
                                           flash_attention)
from repro.kernels.matmul_fused import matmul_fused, matmul_fused_naive
from repro.kernels.moe_experts import moe_experts
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

BF16 = jnp.bfloat16
BIAS_GELU = [EpilogueOp("bias_add", operand="bias"), EpilogueOp("gelu")]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", [matmul_fused, matmul_fused_naive],
                         ids=["blockspec", "naive"])
def test_gemm_bias_gelu_bench_dims(one_chip, kernel):
    m, n, k = 4096, 4096, 1024

    def fn(a, b, bias):
        return kernel(a, b, epilogue=BIAS_GELU, operands={"bias": bias},
                      interpret=False)
    text = _compile_text(fn, one_chip, ((m, k), BF16), ((k, n), BF16),
                         ((n,), BF16))
    assert "tpu_custom_call" in text


def test_gemm_row_reduction(one_chip):
    def fn(a, b):
        return matmul_fused_naive(a, b, reduction="sum", interpret=False)
    text = _compile_text(fn, one_chip, ((1024, 512), BF16), ((512, 1024), BF16))
    assert "tpu_custom_call" in text


def test_flash_attention_olmo_prefill(one_chip):
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)
    qkv = ((1, 16, 2048, 128), BF16)
    assert "tpu_custom_call" in _compile_text(fn, one_chip, qkv, qkv, qkv)


def test_decode_attention_with_lengths(one_chip):
    def fn(q, k, v, lengths):
        return decode_attention(q, k, v, lengths=lengths, interpret=False)
    kv = ((4, 16, 2048, 128), BF16)
    text = _compile_text(fn, one_chip, ((4, 16, 128), BF16), kv, kv,
                         ((4,), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_attention_gqa_head_64(one_chip):
    """LFM2-8B-A1B's decode: 32 query heads over 8 KV heads, head 64, at
    its cell's 256 slots and KV length 384."""
    def fn(q, k, v, lengths):
        return decode_attention(q, k, v, lengths=lengths, interpret=False)
    kv = ((256, 8, 384, 64), BF16)
    text = _compile_text(fn, one_chip, ((256, 32, 64), BF16), kv, kv,
                         ((256,), jnp.int32))
    assert "tpu_custom_call" in text


def test_moe_experts_lfm2_share(one_chip):
    """The 8 experts one chip holds of an LFM2-8B-A1B layer (d 2048, width
    1792) over a decode step's 256 tokens."""
    def fn(x, counts, wi, wg, wo):
        return moe_experts(x, counts, wi, wg, wo, interpret=False)
    e, c, d, f = 8, 256, 2048, 1792
    text = _compile_text(fn, one_chip, ((e, c, d), BF16), ((e,), jnp.int32),
                         ((e, d, f), BF16), ((e, d, f), BF16),
                         ((e, f, d), BF16))
    assert "tpu_custom_call" in text


def test_ssd_scan_mamba2(one_chip):
    """mamba2-780m's heads (48 of 64 wide, state 128) over 2048 steps."""
    def fn(x, dt, a, b, c):
        return ssd_scan(x, dt, a, b, c, chunk=128, interpret=False)
    bh, l, p, n = 48, 2048, 64, 128
    text = _compile_text(fn, one_chip, ((bh, l, p), BF16),
                         ((bh, l), jnp.float32), ((bh, 1), jnp.float32),
                         ((bh, l, n), BF16), ((bh, l, n), BF16))
    assert "tpu_custom_call" in text


def test_attention_unoptimized(one_chip):
    """The optimizer's starting point for flash attention, at OLMo's heads
    over 1024 positions."""
    def fn(q, k, v):
        return attention_unoptimized(q, k, v, causal=True, interpret=False)
    qkv = ((1, 16, 1024, 128), BF16)
    assert "tpu_custom_call" in _compile_text(fn, one_chip, qkv, qkv, qkv)


def test_rmsnorm(one_chip):
    def fn(x, w):
        return rmsnorm(x, w, interpret=False)
    text = _compile_text(fn, one_chip, ((4096, 2048), BF16), ((2048,), BF16))
    assert "tpu_custom_call" in text


def test_elementwise_chain(one_chip):
    def fn(x, bias):
        return elementwise_chain(x, BIAS_GELU, operands={"bias": bias},
                                 interpret=False)
    text = _compile_text(fn, one_chip, ((4096, 4096), BF16), ((4096,), BF16))
    assert "tpu_custom_call" in text


def test_naive_gemm_gpu_default_k_tile(one_chip):
    """The naive input's GPU-default 32-wide K tile is not lane-aligned."""
    def fn(a, b):
        return matmul_fused_naive(a, b, block_k=32, interpret=False)
    text = _compile_text(fn, one_chip, ((256, 128), BF16), ((128, 256), BF16))
    assert "tpu_custom_call" in text
