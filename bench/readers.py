"""The arithmetic behind the per-layer metrics. Each file in
``bench/metrics/`` names one metric and calls one of these; each returns
None where its run holds nothing to read.
"""

from __future__ import annotations

import math
import re

from bench import work
from bench.peaks import least_time_s
from bench.trace import KERNEL

RESULT_SHAPE = re.compile(r"^%\S+ = \w+\[([\d,]*)\]")


def attention_kernel(head_dim: int):
    """Matches the trace's attention kernels: Pallas calls whose result
    rows are one head wide. No other kernel of a decoder's step gives a
    result of that width (the GEMMs' are d_model, d_ff or vocab wide)."""
    def match(name: str) -> bool:
        m = RESULT_SHAPE.match(name)
        return KERNEL in name and m is not None and m.group(1) != "" \
            and int(m.group(1).split(",")[-1]) == head_dim
    return match


def idle_share(ctx):
    """Percent of the traced window in which no operation ran on the
    device (mean over the chips used)."""
    s = ctx.trace_summary
    if s is None or s.window_s <= 0 or s.busy_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)


def l2_roofline(ctx):
    """Geometric mean over problems of least time / device time per call.
    A problem's device time is that of the programs launched inside the
    host spans of its calls."""
    s = ctx.trace_summary
    calls = ctx.records.get("calls")
    if s is None or not calls:
        return None
    busy = s.span_module_s()
    if busy is None:
        return None
    logs = []
    for p in ctx.cfg["problems"]:
        dev = busy.get(f"bench.call.{p['name']}", 0.0)
        if dev <= 0 or not calls.get(p["name"]):
            return None
        flops, nbytes = work.l2_work(p, ctx.cfg["dims"])
        logs.append(math.log(least_time_s(flops, nbytes, ctx.peaks)
                             / (dev / calls[p["name"]])))
    return 100.0 * math.exp(sum(logs) / len(logs))


def attn_roofline(ctx):
    """Least time of the attention work the served tokens need, over the
    attention kernels' device time in the trace."""
    s = ctx.trace_summary
    reqs = ctx.records.get("requests")
    if s is None or not reqs:
        return None
    kernel_s = s.op_seconds(attention_kernel(ctx.cfg["head_dim"]))
    if kernel_s <= 0:
        return None
    flops = nbytes = 0.0
    for prompt, generated, _, _ in reqs:
        f, b = work.request_attention_work(ctx.cfg, prompt, generated)
        flops, nbytes = flops + f, nbytes + b
    return 100.0 * least_time_s(flops, nbytes, ctx.peaks) / kernel_s


def serve_mfu(ctx):
    """Flops the completed requests need, over the time from the window's
    start to the last completion at the chip's bf16 peak."""
    reqs = ctx.records.get("requests")
    if not reqs:
        return None
    flops = sum(work.request_flops(ctx.cfg, p, g) for p, g, _, _ in reqs)
    return 100.0 * flops / (ctx.records["elapsed_s"]
                            * ctx.peaks["bf16_flops"] * ctx.chips)
