"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e (``data/``)."""

import pathlib
from types import SimpleNamespace as NS

import pytest

from bench import readers, trace


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _profile():
    ops = [_ev("%while.1 = (...) while(...)", 0, 180),
           _ev("%fusion.1 = f32[8]", 0, 100),
           _ev("%k = bf16[4,1,128] custom-call(), tpu_custom_call", 100, 50),
           _ev("%all-reduce.3 = f32[8]", 140, 40),
           _ev("%fusion.1 = f32[8]", 300, 100)]
    host = [_ev("bench.wave", 1000, 250), _ev("bench.wait", 1250, 60),
            _ev("other", 1000, 10), _ev(trace.LAUNCH, 1005, 3),
            _ev(trace.LAUNCH, 1290, 3)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="XLA Modules", events=[_ev("jit_a", 0, 180),
                                           _ev("jit_b", 300, 100)])]),
        NS(name="/device:TPU:1", lines=[NS(name="XLA Ops",
                                           events=[_ev("fusion.9", 0, 200)])]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
    ])


def test_union_and_covered():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert trace.covered(merged, 2, 6) == 2
    assert trace.covered(merged, -5, 50) == 6


def test_summary_of_hand_made_events():
    s = trace.summarize(_profile(), window_s=500e-9, chips=2)
    # TPU:0 busy 0-180 and 300-400 = 280 ns; TPU:1 200 ns; mean 240 ns
    assert s.busy_s == pytest.approx(240e-9)
    # the while loop holds other operations: only its leaves count
    assert s.top_ops(2) == [["%fusion.1", pytest.approx(200e-9)],
                            ["fusion.9", pytest.approx(200e-9)]]
    assert s.op_seconds(lambda n: trace.KERNEL in n) == pytest.approx(50e-9)
    # launches pair with modules in order: jit_a was launched in
    # bench.wave, jit_b in bench.wait; the device runs 1005 ns early
    assert s.span_module_s() == {"bench.wave": pytest.approx(180e-9),
                                 "bench.wait": pytest.approx(100e-9)}
    assert s.skew_ns() == -1005
    # the one gap (180-300) has its middle, 240 + 1005, inside bench.wave
    assert s.top_gaps(5) == [["bench.wave", pytest.approx(120e-9)]]


def test_one_chip_summary_ignores_other_devices():
    s = trace.summarize(_profile(), window_s=500e-9, chips=1)
    assert s.busy_s == pytest.approx(280e-9)


DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_recorded_gemm_calls():
    """Three calls of a jitted 4096x1024x4096 bf16 matmul + tanh, each in
    a bench.call.<i> span, recorded on a TPU v5e."""
    s = trace.summarize(trace.load_file(DATA / "v5e_gemm_calls.xplane.pb"),
                        window_s=0.05, chips=1)
    per_call = s.span_module_s()
    assert sorted(per_call) == ["bench.call.0", "bench.call.1",
                                "bench.call.2"]
    # 34.4 GFLOP at 197 TFLOP/s is 174 us; the chip took about 182 us
    for v in per_call.values():
        assert 174e-6 < v < 200e-6
    assert 3 * 174e-6 < s.busy_s < 3 * 200e-6


# OLMo-1B decode steps (KV 384, Pallas decode attention) recorded on a TPU
# v5e: ten steps at 32 slots, from before the kernel had a name of its own;
# and one wave of the batch serving cell at 64 slots (two prompt and three
# answer tokens a request, so four steps), named ``decode_attention``.
DECODE_TRACES = {
    "v5e_decode_steps": dict(window_s=0.2, steps=10, name="%closed_call",
                             kernel_s=(0.030, 0.050), busy_s=(0.15, 0.2)),
    "v5e_serve_batch_wave": dict(window_s=0.18183831300000008, steps=4,
                                 name="%decode_attention",
                                 kernel_s=(0.025, 0.045),
                                 busy_s=(0.14, 0.18)),
}


@pytest.mark.parametrize("name", sorted(DECODE_TRACES))
def test_recorded_decode_steps(name):
    t = DECODE_TRACES[name]
    s = trace.summarize(trace.load_file(DATA / f"{name}.xplane.pb"),
                        window_s=t["window_s"], chips=1)
    match = readers.attention_kernel(128)
    kernel = [n for n, *_ in s.ops if match(n)]
    assert len(kernel) == t["steps"] * 16     # one call per layer per step
    assert all(n.startswith(t["name"]) for n in kernel)
    assert t["kernel_s"][0] < s.op_seconds(match) < t["kernel_s"][1]
    assert not any(n.startswith("%while") for n, *_ in s.ops)
    assert s.top_ops(1)[0][0] == kernel[0].split(" = ")[0]
    assert t["busy_s"][0] < s.busy_s < t["busy_s"][1]
