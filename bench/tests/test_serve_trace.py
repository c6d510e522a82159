"""The batch serving cell's per-layer readers on a trace recorded on a TPU
v5e: one wave at the cell's 64 slots and KV length 384 (two prompt tokens,
three answer tokens a request, so four decode steps), with the records the
harness kept for it."""

import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import peaks, run, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "olmo-1b.serve.batch"
RANGE = {"idle_share.batch": (0.0, 100.0),
         "attn_roofline.batch": (0.0, 100.0),
         "serve_mfu.batch": (0.0, 100.0)}


@pytest.fixture(scope="module")
def ctx():
    records = json.loads((DATA / "v5e_serve_batch_wave.json").read_text())
    cell = run.load_cell(CELL)
    summary = trace.summarize(
        trace.load_file(DATA / "v5e_serve_batch_wave.xplane.pb"),
        records["window_s"], cell["cell"]["chips"])
    return SimpleNamespace(cfg=cell["config"], records=records,
                           trace_summary=summary, chips=1,
                           peaks=peaks.peaks_for("TPU v5 lite"))


def test_recorded_wave_pairs_launches_with_modules(ctx):
    s = ctx.trace_summary
    assert len(ctx.records["requests"]) == 64
    assert len(s.modules[0]) == len(s.launches) > 0
    assert 0 < s.busy_s < s.window_s


@pytest.mark.parametrize("name", sorted(RANGE))
def test_reader_reads_a_number_in_range(ctx, name):
    assert name in {m["name"] for m in run.load_cell(CELL)["per_layer"]}
    lo, hi = RANGE[name]
    v = run.read_metric(name, ctx)
    assert isinstance(v, float) and lo < v <= hi

