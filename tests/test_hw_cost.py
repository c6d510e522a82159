"""Hardware query system + cost model: structural properties the optimizer
relies on (hypothesis-driven where shapes vary)."""

import pytest
from hypothesis import given, settings, strategies as st

from repro.hw.query import HardwareQuery
from repro.hw.specs import (TPU_V5E, dtype_itemsize, get_spec,
                             spec_for_device, spec_for_kind)
from repro.ir import GraphBuilder
from repro.ir.cost import CostModel, graph_flops
from repro.ir.schedule import (FusionGroup, KernelProgram, PallasConfig,
                               Schedule, eager_schedule)

HW = HardwareQuery(TPU_V5E)
CM = CostModel(TPU_V5E)


@settings(max_examples=40, deadline=None)
@given(m=st.integers(8, 16384), n=st.integers(128, 16384),
       k=st.integers(128, 16384),
       dtype=st.sampled_from(["bfloat16", "float32"]))
def test_optimal_params_always_valid(m, n, k, dtype):
    p = HW.get_optimal_params(m, n, k, dtype)
    sub, lane = TPU_V5E.min_tile(dtype)
    assert p.block_m >= 1 and p.block_n >= 1 and p.block_k >= 1
    assert p.block_m % sub == 0 or p.block_m >= m  # clamped tiny dims allowed
    assert p.block_n % lane == 0 or p.block_n >= n
    # VMEM budget always holds
    assert p.working_set_bytes(dtype_itemsize(dtype)) <= TPU_V5E.vmem_bytes
    # swizzle guard: never swizzle a single M-tile
    if -(-m // p.block_m) <= 1:
        assert p.group_m == 1


def test_skinny_matrices_get_asymmetric_tiles():
    tall = HW.get_optimal_params(65536, 512, 1024, "bfloat16")
    wide = HW.get_optimal_params(512, 65536, 1024, "bfloat16")
    assert tall.block_m >= tall.block_n
    assert wide.block_n >= wide.block_m


def test_autotune_grid_valid_and_bounded():
    grid = HW.autotune_grid(4096, 4096, 4096, "bfloat16")
    assert 1 <= len(grid) <= 12
    for p in grid:
        assert p.working_set_bytes(2) <= TPU_V5E.vmem_bytes


def _program(dtype="float32", impl="pallas_blockspec", cfg=None,
             m=2048, n=2048, k=2048):
    b = GraphBuilder("p", dtype=dtype)
    x = b.input((m, k), name="x")
    w = b.param((k, n), name="w")
    mm = b.matmul(x, w, name="mm")
    g = b.done(b.gelu(mm, name="act"))
    sched = eager_schedule(g)
    for grp in sched.groups:
        if grp.root == "mm":
            grp.impl = impl
            grp.config = cfg or PallasConfig(512, 512, 512, num_stages=2)
    return KernelProgram("p", g, sched, original_flops=graph_flops(g))


def test_bf16_faster_than_f32():
    p32 = _program()
    pbf = _program()
    pbf.schedule.compute_dtype = "bfloat16"
    assert CM.program_time(pbf) < CM.program_time(p32)


def test_f64_much_slower():
    assert CM.program_time(_program("float64")) > 2 * CM.program_time(_program())


def test_blockspec_beats_naive():
    naive = _program(impl="pallas_naive",
                     cfg=PallasConfig(128, 128, 32, num_stages=1))
    modern = _program()
    assert CM.program_time(modern) < CM.program_time(naive)


def test_fusion_reduces_time():
    p = _program()
    fused = _program()
    g = fused.schedule.groups
    mm_grp = next(x for x in g if x.root == "mm")
    act_grp = next(x for x in g if x.root == "act")
    mm_grp.nodes.append("act")
    fused.schedule.groups.remove(act_grp)
    assert CM.program_time(fused) < CM.program_time(p)


def test_persistent_removes_spills():
    base = _program(cfg=PallasConfig(512, 512, 256, num_stages=2,
                                     persistent=False), k=8192)
    pers = _program(cfg=PallasConfig(512, 512, 256, num_stages=2,
                                     persistent=True), k=8192)
    cb = CM.program_cost(base)
    cp = CM.program_cost(pers)
    assert cp.hbm_bytes < cb.hbm_bytes


def test_swizzle_reduces_traffic():
    no = _program(cfg=PallasConfig(256, 256, 2048, group_m=1), m=8192, n=8192)
    sw = _program(cfg=PallasConfig(256, 256, 2048, group_m=8), m=8192, n=8192)
    assert CM.program_cost(sw).hbm_bytes < CM.program_cost(no).hbm_bytes


def test_xla_reduction_epilogue_materializes():
    """XLA cannot elide the GEMM product across a reduction epilogue; a
    pallas group can (the paper's fusion-mode distinction)."""
    def build(impl):
        b = GraphBuilder("p")
        x = b.input((4096, 512), name="x")
        w = b.param((512, 8192), name="w")
        mm = b.matmul(x, w, name="mm")
        g = b.done(b.reduce_max(mm, axes=(1,), name="red"))
        sched = Schedule(groups=[FusionGroup("g0", ["mm", "red"], "mm", impl,
                                             PallasConfig(512, 512, 512))])
        return KernelProgram("p", g, sched, original_flops=graph_flops(g))
    assert (CM.program_cost(build("pallas_blockspec")).hbm_bytes
            < CM.program_cost(build("xla")).hbm_bytes)


def test_specs_table():
    assert get_spec("v5e").peak_flops_bf16 == pytest.approx(197e12)
    assert get_spec("tpu_v5e").hbm_bw == pytest.approx(819e9)
    with pytest.raises(KeyError):
        get_spec("h100")


def test_spec_resolved_from_device_kind():
    assert spec_for_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="no spec for device_kind"):
        spec_for_kind("TPU v9 hyper")


def test_spec_for_device_refuses_a_cpu():
    with pytest.raises(RuntimeError, match="no TPU attached"):
        spec_for_device()
