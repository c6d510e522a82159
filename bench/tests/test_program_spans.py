"""The reduction of what the host does inside each call, on hand-made
events and on a trace recorded on a TPU v5e (``data/``)."""

import pathlib
import shutil
from types import SimpleNamespace as NS

import pytest

from bench import program_spans as ps
from bench import run, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRICS = ("lowering_idle_share.kernels", "lowerings_per_call.kernels",
           "launches_per_call.kernels")


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def _profile(modules=((0, 100), (300, 400)), calls=True):
    """Device 0 busy 0-100 and 300-400 on its clock; launched at 1005 and
    1295 on the host's, so the device clock runs 1005 ns behind."""
    dev = [_ev("%fusion.1 = f32[8]", s, e) for s, e in ((0, 100), (300, 400))]
    host = [_ev(trace.LAUNCH, 1005, 1008), _ev(trace.LAUNCH, 1295, 1298),
            _ev(ps.LOWERING, 1010, 1090),     # device busy all through
            _ev(ps.LOWERING, 1110, 1190),     # device idle all through
            _ev(ps.LOWERING, 1210, 1240),     # outside any call span
            _ev(ps.LOWERING, 1260, 1300),     # idle until 1305
            _ev(ps.COMPILE, 1420, 1450)]
    if calls:
        host += [_ev(ps.CALL + "a", 1000, 1200), _ev(ps.CALL + "b", 1250, 1500),
                 _ev("bench.callback", 1010, 1020)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=dev),
            NS(name="XLA Modules",
               events=[_ev("jit_a", s, e) for s, e in modules])]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=host)]),
    ])


def _reduce(pd):
    return ps.reduce(pd, trace.summarize(pd, window_s=500e-9, chips=1))


def test_hand_made_events():
    r = _reduce(_profile())
    # window 1000-1500; busy on the host's clock 1005-1105 and 1305-1405
    assert (r.calls, r.lowerings, r.compiles, r.launches) == (2, 3, 1, 2)
    assert r.idle_ns == 5 + 200 + 95
    # 1110-1190 and 1260-1300; the first lowering overlaps only busy time
    assert r.idle_lowering_ns == 80 + 40
    # all idle time but 1200-1250, between the two call spans
    assert r.idle_call_ns == 300 - 50


def test_unpaired_launches_leave_only_the_counts():
    r = _reduce(_profile(modules=((0, 100),)))
    assert (r.calls, r.lowerings, r.launches) == (2, 3, 2)
    assert r.idle_ns is r.idle_lowering_ns is r.idle_call_ns is None
    ctx = NS(trace_summary=None)
    for read in (ps.lowering_idle_share, ps.lowerings_per_call,
                 ps.launches_per_call):
        assert read(ctx) is None


def test_no_call_span_reads_nothing():
    assert _reduce(_profile(calls=False)) is None


@pytest.fixture
def recorded(tmp_path):
    """Three problems of forge.l2-gemm at its size, two rounds after two
    warm-up rounds, recorded on a TPU v5e through ``run_program`` as the
    harness calls it (without the Python tracer), as a cell's context."""
    shutil.copy(DATA / "v5e_program_spans.xplane.pb", tmp_path)
    pd = trace.load(tmp_path)
    return NS(trace_summary=trace.summarize(pd, window_s=0.45, chips=1),
              _trace_dir=tmp_path), pd


def test_recorded_counts_by_hand(recorded):
    ctx, pd = recorded
    r = ps.of(ctx)
    # per round: gemm_bias_gelu (one matmul group: 2 launches),
    # gemm_divide_sum (matmul_reduce + xla: 5), matmul_dropout_tanh (one
    # matmul group: 4); one lowering each, no compile (the cache is warm)
    assert (r.calls, r.lowerings, r.compiles, r.launches) == (6, 6, 0, 22)
    assert len(ctx.trace_summary.modules[0]) == 22
    values = {m: run.read_metric(m, ctx) for m in METRICS}
    assert values["lowerings_per_call.kernels"] == 1.0
    assert values["launches_per_call.kernels"] == pytest.approx(22 / 6)
    # the same share from the other side: lowering time inside call spans
    # less what of it the device was busy
    s = ctx.trace_summary
    skew = s.skew_ns()
    busy = [[a - skew, b - skew] for a, b in s.busy[0]]
    low = trace.union(ps.inside(ps.host_spans(pd)[ps.LOWERING],
                                ps.host_spans(pd)[ps.CALL]))
    idle_low = sum(e - b - trace.covered(busy, b, e) for b, e in low)
    assert r.idle_lowering_ns == pytest.approx(idle_low)
    assert values["lowering_idle_share.kernels"] == pytest.approx(
        100 * idle_low / r.idle_ns)
    assert 60 < values["lowering_idle_share.kernels"] < 90
    assert r.idle_lowering_ns < r.idle_call_ns <= r.idle_ns


def test_trace_is_read_once_per_process(recorded, monkeypatch):
    ctx, _ = recorded
    loads = []
    real = trace.load
    monkeypatch.setattr(trace, "load", lambda d: loads.append(d) or real(d))
    for m in METRICS:
        run.read_metric(m, ctx)
    assert len(loads) == 1
