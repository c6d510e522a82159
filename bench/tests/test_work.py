"""Work counts against hand-worked values."""

import json
import pathlib

import pytest

from bench import work
from bench.peaks import least_time_s, peaks_for

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
OLMO = json.loads((CONFIGS / "olmo-1b.json").read_text())
L2 = json.loads((CONFIGS / "aibench-l2-gemm.json").read_text())


def problem(name):
    return next(p for p in L2["problems"] if p["name"] == name)


def test_l2_gemm_bias_gelu():
    flops, nbytes = work.l2_work(problem("gemm_bias_gelu"), L2["dims"])
    # 2*4096*4096*1024 + 10*4096*4096
    assert flops == 34_359_738_368 + 167_772_160
    # bf16: x 4096x1024, w 1024x4096, bias 4096, out 4096x4096
    assert nbytes == 2 * (4_194_304 + 4_194_304 + 4_096 + 16_777_216)


def test_l2_row_sum_is_a_gemv():
    flops, nbytes = work.l2_work(problem("gemm_divide_sum"), L2["dims"])
    assert flops == 2 * 4096 * 1024 + 1024 * 4096 + 4096
    assert nbytes == 2 * (4_194_304 + 4_194_304 + 4_096)


def test_l2_duplicate_branch_counts_one_gemm():
    flops, _ = work.l2_work(problem("gemm_branch_duplicate"), L2["dims"])
    assert flops == 34_359_738_368 + 10 * 16_777_216


def test_every_l2_problem_has_work():
    for p in L2["problems"]:
        flops, nbytes = work.l2_work(p, L2["dims"])
        assert flops > 0 and nbytes > 0, p["name"]


def test_olmo_token_flops():
    # per layer: q, k, v, o (4 * 2048^2) + SwiGLU (3 * 2048 * 8192)
    assert work.token_matmul_flops(OLMO) == 2 * 16 * (4 * 2048 ** 2
                                                     + 3 * 2048 * 8192)
    assert work.token_matmul_flops(OLMO) == 2_147_483_648
    assert work.unembed_flops(OLMO) == 2 * 2048 * 50304


def test_olmo_attention_work():
    # prompt of 2: pairs 1 + 2 = 3; 3 generated, 2 fed back at contexts
    # of 3 and 4 keys: 7 pairs; each pair 4 * 16 layers * 16 heads * 128
    flops, nbytes = work.request_attention_work(OLMO, 2, 3)
    assert flops == 10 * 4 * 16 * 16 * 128
    prompt_qkv = 2 * 16 * (16 + 2 * 16) * 128 * 2
    decode_kv = 7 * 16 * 2 * 16 * 128 * 2
    assert nbytes == prompt_qkv + decode_kv


def test_olmo_request_flops():
    got = work.request_flops(OLMO, 2, 3)
    want = (4 * 2_147_483_648 + 10 * 4 * 16 * 16 * 128
            + 3 * 2 * 2048 * 50304)
    assert got == want


def test_least_time_takes_the_larger_bound():
    v5e = peaks_for("TPU v5 lite")
    assert least_time_s(197e12, 0, v5e) == pytest.approx(1.0)
    assert least_time_s(0, 819e9, v5e) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_formula_refuses_code():
    with pytest.raises(ValueError):
        work.formula("__import__('os')", {})
