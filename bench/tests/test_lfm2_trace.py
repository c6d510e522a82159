"""The LFM2 cell's per-layer readers on a trace recorded on a TPU v5e by
``data/v5e_lfm2_wave/record.py``: one wave at the cell's 256 slots and KV
length 384 (two prompt tokens, three answer tokens a request, so four decode
steps), with the decode calls and the program's counters of the held
experts' work that the harness took from it."""

import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import lfm2_work, peaks, run, trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "v5e_lfm2_wave"
CELL = "lfm2-8b-a1b.serve.batch"
MOE = run.BENCH / "metrics" / "moe_roofline.lfm2-batch.py"


@pytest.fixture(scope="module")
def ctx():
    records = json.loads((DATA / "v5e_lfm2_wave.json").read_text())
    cell = run.load_cell(CELL)
    summary = trace.summarize(
        trace.load_file(DATA / "v5e_lfm2_wave.xplane.pb"),
        records["window_s"], cell["cell"]["chips"])
    return SimpleNamespace(cfg=cell["config"], records=records,
                           trace_summary=summary, chips=1, _trace_dir=DATA,
                           peaks=peaks.peaks_for("TPU v5 lite"))


def _reader():
    import importlib.util
    spec = importlib.util.spec_from_file_location("moe_reader", MOE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_recorded_wave_counts_four_steps_of_256(ctx):
    assert ctx.records["decode_calls"] == 4 and ctx.records["requests"] == 256
    s = ctx.trace_summary
    assert len(s.modules[0]) == len(s.launches) > 0
    assert 0 < s.busy_s < s.window_s


def test_one_moe_experts_call_per_moe_layer_and_step(ctx):
    mod = _reader()
    pd = trace.load(DATA)
    dev0 = next(p for p in pd.planes if p.name == "/device:TPU:0")
    calls = [ev for line in dev0.lines if line.name == trace.OPS_LINE
             for ev in line.events if mod.is_moe_experts(ev.name)]
    assert len(calls) == ctx.records["decode_calls"] * 22
    kernel_s, kinds = mod.expert_calls(pd, 22)
    # whole kernels: at least the time of the leaf operations of that name
    leaf_s = ctx.trace_summary.op_seconds(mod.is_moe_experts)
    assert kernel_s >= leaf_s > 0
    # XLA brings a conv layer's wi into VMEM before the call; after an
    # attention layer (2, 6, 10, 14, 18, 21) all three stay in HBM; the
    # rows in and out are in VMEM
    after_attn = {i - 2 for i in (2, 6, 10, 14, 18, 21)}
    assert kinds == [(3 if k in after_attn else 2, 0) for k in range(22)]


def test_moe_roofline_counts_only_what_the_calls_read_from_hbm(ctx):
    """A call after an attention layer reads every held expert's three
    matrices from HBM, one after a conv layer two. In the recorded wave
    every held expert had rows in every call, and the share lies below
    100 %; counting every matrix from HBM would put it above."""
    rec, steps = ctx.records, ctx.records["decode_calls"]
    assert rec["expert_reads_by_layer"] == [8 * steps] * 22
    share = run.read_metric("moe_roofline.lfm2-batch", ctx)
    assert share == pytest.approx(90.966, abs=0.01)
    kernel_s, _ = _reader().expert_calls(trace.load(DATA), 22)
    every = lfm2_work.experts_work(ctx.cfg, rec["expert_rows"],
                                   rec["expert_reads"])
    assert 100 * peaks.least_time_s(*every, ctx.peaks) / kernel_s > 105


def test_moe_roofline_needs_the_programs_counters(ctx):
    bare = SimpleNamespace(**vars(ctx))
    bare.records = {k: v for k, v in ctx.records.items()
                    if not k.startswith("expert_")}
    assert run.read_metric("moe_roofline.lfm2-batch", bare) is None


def test_idle_share_reads_the_recorded_wave(ctx):
    assert run.read_metric("idle_share.lfm2-batch", ctx) == pytest.approx(
        51.84282128883508, rel=1e-9)
