"""What the host does inside each call of the program, on the device's clock.

The ``kernels`` harness writes a ``bench.call.<problem>`` span around each
``run_program`` call and its ``block_until_ready`` (``jax.profiler.
TraceAnnotation``). JAX itself writes, on the same host plane (names as a
TPU v5e trace has them):

- ``lower_sharding_computation``: one per lowering of a computation to
  StableHLO, here each eager Pallas call whose kernel closure is new;
- ``backend_compile_and_load``: one per compile, which a hit of the
  persistent compilation cache skips;
- ``PJRT_LoadedExecutable_Execute``: one per program launched on the device
  (``bench.trace.LAUNCH``).

This module counts them inside the call spans, and maps device 0's idle
time onto the host's clock with ``Summary.skew_ns`` (the launch-to-module
pairing that ``l2_gemm_roofline`` relies on), so that each idle interval is
split by what the host spans cover of it. The xplane is read once per
process, however many readers ask. Every reader returns None where the run
holds no call span, and the idle share also where launches and modules do
not pair up. The program writes no span of its own: one around the body
of ``run_program`` made untraced calls ≈ 3 % slower on a TPU v5e.

    python3 -m bench.program_spans <trace dir>

prints the reduction of the newest trace under a directory.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import sys

from bench import trace

CALL = "bench.call."            # prefix of the harness's span per call
LOWERING = "lower_sharding_computation"
COMPILE = "backend_compile_and_load"


@dataclasses.dataclass
class CallSpans:
    calls: int                  # call spans
    lowerings: int              # lowering spans that start inside them
    compiles: int               # compile spans that start inside them
    launches: int               # launches inside them
    idle_ns: float | None       # device 0's idle time in the window
    idle_lowering_ns: float | None   # of which lowering covers
    idle_call_ns: float | None       # of which any call span covers

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def host_spans(pd) -> dict:
    """``CALL``, ``LOWERING``, ``COMPILE`` -> sorted (start_ns, end_ns) of
    the host events of that name (``CALL``: that prefix), all threads."""
    out = {n: [] for n in (CALL, LOWERING, COMPILE)}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    key = CALL if ev.name.startswith(CALL) else ev.name
                    if key in out:
                        out[key].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return {n: sorted(v) for n, v in out.items()}


def inside(events, calls) -> list:
    """The events that start inside a call span."""
    starts = [s for s, _ in calls]
    out = []
    for s, e in events:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < calls[i][1]:
            out.append((s, e))
    return out


def idle_intervals(summary, lo: float, hi: float, device: int = 0):
    """Device ``device``'s idle intervals in [lo, hi) of the host clock;
    None where launches and modules do not pair up."""
    mods = summary.modules.get(device, [])
    if not mods or len(mods) != len(summary.launches):
        return None
    skew = summary.skew_ns(device)
    out, t = [], lo
    for s, e in summary.busy.get(device, []):
        s, e = s - skew, e - skew
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = e
    if t < hi:
        out.append((t, hi))
    return out


def reduce(pd, summary) -> CallSpans | None:
    """The counts and idle coverage of a trace; None where it holds no
    call span. The window is the host's, from the first call span's start
    to the last one's end."""
    spans = host_spans(pd)
    calls = spans[CALL]
    if not calls:
        return None
    lowering = inside(spans[LOWERING], calls)
    launches = inside([(t, t) for t in summary.launches], calls)
    idle = idle_intervals(summary, calls[0][0], max(e for _, e in calls))
    idle_ns = idle_low = idle_call = None
    if idle is not None:
        low_m, call_m = trace.union(lowering), trace.union(calls)
        low_ends, call_ends = [e for _, e in low_m], [e for _, e in call_m]
        idle_ns = float(sum(e - s for s, e in idle))
        idle_low = sum(trace.covered(low_m, s, e, low_ends) for s, e in idle)
        idle_call = sum(trace.covered(call_m, s, e, call_ends)
                        for s, e in idle)
    return CallSpans(
        calls=len(calls), lowerings=len(lowering),
        compiles=len(inside(spans[COMPILE], calls)),
        launches=len(launches), idle_ns=idle_ns,
        idle_lowering_ns=idle_low, idle_call_ns=idle_call)


_REDUCED: dict = {}


def of(ctx) -> CallSpans | None:
    """The reduction of the cell's trace, read once per process."""
    s, trace_dir = ctx.trace_summary, getattr(ctx, "_trace_dir", None)
    if s is None or trace_dir is None:
        return None
    key = str(trace_dir)
    if key not in _REDUCED:
        _REDUCED[key] = reduce(trace.load(trace_dir), s)
    return _REDUCED[key]


def lowering_idle_share(ctx):
    """Percent of the device's idle time in the window that lowering inside
    the ``run_program`` calls covers."""
    r = of(ctx)
    if r is None or not r.idle_ns:
        return None
    return 100.0 * r.idle_lowering_ns / r.idle_ns


def lowerings_per_call(ctx):
    """Lowering spans inside call spans, per call span."""
    r = of(ctx)
    return None if r is None else r.lowerings / r.calls


def launches_per_call(ctx):
    """Device launches inside call spans, per call span; None where the
    trace holds no launch at all."""
    r = of(ctx)
    if r is None or not ctx.trace_summary.launches:
        return None
    return r.launches / r.calls


def main(argv=None):
    trace_dir, = argv if argv is not None else sys.argv[1:]
    pd = trace.load(trace_dir)
    r = reduce(pd, trace.summarize(pd, window_s=0.0, chips=1))
    print(json.dumps(r.as_dict() if r else None))


if __name__ == "__main__":
    main()
