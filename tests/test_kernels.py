"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.epilogue import EpilogueOp
from repro.kernels.decode_attention import decode_attention
from repro.kernels.elementwise import elementwise_chain
from repro.kernels.flash_attention import attention_unoptimized, flash_attention
from repro.kernels.matmul_fused import matmul_fused, matmul_fused_naive
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan


def _arr(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


EPI = [EpilogueOp("bias_add", operand="bias"), EpilogueOp("gelu"),
       EpilogueOp("scale", value=0.5)]


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 384, 192),
                                   (256, 300, 192), (200, 256, 160)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_fused_shapes_dtypes(rng, m, n, k, dtype):
    a, b = _arr(rng, m, k, dtype=dtype), _arr(rng, k, n, dtype=dtype)
    bias = _arr(rng, n, dtype=dtype)
    want = ref.matmul_fused_ref(a, b, EPI, {"bias": bias})
    got = matmul_fused(a, b, block_m=128, block_n=128, block_k=64,
                       epilogue=EPI, operands={"bias": bias},
                       out_dtype=jnp.float32)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("reduction", ["sum", "max", "min", "mean"])
def test_matmul_reduction_epilogue(rng, reduction):
    a, b = _arr(rng, 256, 192), _arr(rng, 192, 320)
    want = ref.matmul_fused_ref(a, b, [EpilogueOp("gelu")], {},
                                reduction=reduction)
    got = matmul_fused(a, b, block_m=128, block_n=128, block_k=64,
                       epilogue=[EpilogueOp("gelu")], reduction=reduction)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_matmul_swizzle_equivalence(rng):
    """GROUP_M traversal must not change results."""
    a, b = _arr(rng, 512, 256), _arr(rng, 256, 512)
    base = matmul_fused(a, b, block_m=128, block_n=128, block_k=128, group_m=1)
    for gm in (2, 4, 8):
        got = matmul_fused(a, b, block_m=128, block_n=128, block_k=128,
                           group_m=gm)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=1e-6)


def test_matmul_naive_requires_divisible(rng):
    a, b = _arr(rng, 200, 128), _arr(rng, 128, 256)
    with pytest.raises(ValueError, match="boundary"):
        matmul_fused_naive(a, b, block_m=128, block_n=128, block_k=64)


@settings(max_examples=10, deadline=None)
@given(sq=st.sampled_from([64, 128, 256]), skv=st.sampled_from([128, 256]),
       h=st.sampled_from([4, 8]), hkv=st.sampled_from([1, 2, 4]),
       causal=st.booleans(), seed=st.integers(0, 99))
def test_flash_attention_property(sq, skv, h, hkv, causal, seed):
    if h % hkv:
        return
    if causal and sq > skv:
        return  # queries preceding the KV window are fully masked (NaN ref)
    rng = np.random.default_rng(seed)
    d = 64
    q = _arr(rng, 2, h, sq, d)
    k = _arr(rng, 2, hkv, skv, d)
    v = _arr(rng, 2, hkv, skv, d)
    kk = jnp.repeat(k, h // hkv, axis=1)
    vv = jnp.repeat(v, h // hkv, axis=1)
    want = ref.attention_ref(q, kk, vv, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_windowed(rng):
    q = _arr(rng, 1, 4, 256, 64)
    k = _arr(rng, 1, 4, 256, 64)
    v = _arr(rng, 1, 4, 256, 64)
    want = ref.attention_ref(q, k, v, causal=True, window=64)
    got = flash_attention(q, k, v, causal=True, window=64,
                          block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_unoptimized_matches_flash(rng):
    """The 'original' kernel and the optimized kernel agree (the paper's
    correctness-across-the-before/after-pair requirement)."""
    q = _arr(rng, 2, 4, 128, 64)
    k = _arr(rng, 2, 2, 128, 64)
    v = _arr(rng, 2, 2, 128, 64)
    a = attention_unoptimized(q, k, v, causal=True)
    b = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)


def test_decode_attention_ragged_lengths(rng):
    q = _arr(rng, 4, 8, 64)
    k = _arr(rng, 4, 2, 512, 64)
    v = _arr(rng, 4, 2, 512, 64)
    lengths = jnp.array([512, 300, 17, 1], jnp.int32)
    kk, vv = jnp.repeat(k, 4, axis=1), jnp.repeat(v, 4, axis=1)
    want = ref.decode_attention_ref(q, kk, vv, lengths=lengths)
    got = decode_attention(q, k, v, lengths=lengths, block_kv=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rows,d", [(64, 128), (512, 384), (100, 256)])
def test_rmsnorm_sweep(rng, rows, d):
    x, w = _arr(rng, rows, d), _arr(rng, d)
    np.testing.assert_allclose(np.asarray(rmsnorm(x, w, block_rows=64)),
                               np.asarray(ref.rmsnorm_ref(x, w)),
                               rtol=1e-5, atol=1e-5)


def test_elementwise_chain_with_operands(rng):
    x = _arr(rng, 256, 192)
    r = _arr(rng, 256, 192)
    epi = [EpilogueOp("mul", operand="r"), EpilogueOp("tanh"),
           EpilogueOp("clamp_min", value=-0.5)]
    got = elementwise_chain(x, epi, operands={"r": r}, block_rows=64)
    want = ref.elementwise_chain_ref(x, epi, {"r": r})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(l=st.sampled_from([64, 128, 256]), chunk=st.sampled_from([32, 64]),
       seed=st.integers(0, 20))
def test_ssd_scan_property(l, chunk, seed):
    rng = np.random.default_rng(seed)
    B, H, P, N = 2, 2, 16, 32
    x = _arr(rng, B, l, H, P)
    dt = jnp.abs(_arr(rng, B, l, H)) * 0.1 + 0.01
    a = -jnp.abs(_arr(rng, H)) - 0.1
    bm = _arr(rng, B, l, N) * 0.3
    cm = _arr(rng, B, l, N) * 0.3
    want_y, want_s = ref.ssd_ref(x, dt, a, bm, cm)
    from repro.kernels.ops import ssd
    got_y, got_s = ssd(x, dt, a, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=5e-4, atol=5e-4)


def test_interpret_resolved_from_the_backend(monkeypatch):
    from repro.kernels.platform import resolve_interpret
    assert resolve_interpret() is True            # the CPU backend
    assert resolve_interpret(False) is False      # explicit wins
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
