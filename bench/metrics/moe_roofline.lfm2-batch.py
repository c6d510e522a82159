"""The held experts' kernel's share of its roofline over the window: the
least time of the work its calls did, max(flops / peak, HBM bytes /
bandwidth), over the device time of the trace's ``%moe_experts.<n>``
operations on the first chip.

The work is counted per held-expert layer (bench.lfm2_work), from the
program's counters (the rows each layer's calls computed, and the (call,
expert) pairs that had rows) and from the layer's call in the trace. XLA
may copy an operand of the call into VMEM (layout ``S(1)``) with async
copies that run during earlier operations: the v5e trace shows each conv
layer's ``wi`` brought in so. Those bytes are read outside the kernel's
interval, so only the operands the call's text leaves in HBM count: of the
last three (``wi``, ``wg``, ``wo``), and of the rows in (the fourth from
last) and out (the result). The n-th call of a step is the n-th held-expert
layer: each takes the output of the one before. Whole durations count: other
operations can start inside a kernel's interval, which the summary's leaf
operations would leave out. A program without the counters, or a trace
whose calls do not pair with them, gives nothing to read."""

import bisect
import re

from bench import lfm2_work, trace
from bench.peaks import least_time_s

ARRAY = re.compile(r"\w+\[[\d,]*\]\{([^}]*)\}")
OPERAND = re.compile(r"\w+\[[\d,]*\]\{([^}]*)\} %")
SPACE = re.compile(r"S\((\d+)\)")


def is_moe_experts(name: str) -> bool:
    return name.startswith("%moe_experts") and trace.KERNEL in name


def in_hbm(layout: str) -> bool:
    m = SPACE.search(layout)
    return m is None or m.group(1) == "0"


def hbm_operands(text: str):
    """(weight matrices, row arrays) of a ``moe_experts`` call that its HLO
    text leaves in HBM; None where the text holds too few operands."""
    head, _, args = text.partition(" custom-call(")
    result = ARRAY.search(head)
    layouts = OPERAND.findall(args)
    if result is None or len(layouts) < 4:
        return None
    mats = sum(in_hbm(lay) for lay in layouts[-3:])
    return mats, in_hbm(layouts[-4]) + in_hbm(result.group(1))


def expert_calls(pd, layers: int):
    """Device seconds of the ``moe_experts`` operations of chip 0, and per
    held-expert layer what its call leaves in HBM; None where no step holds
    one call a layer, or a call is not one of them."""
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not (m and int(m.group(1)) == 0):
            continue
        evs, mods = [], []
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                evs += [(ev.start_ns, ev.duration_ns, ev.name)
                        for ev in line.events if is_moe_experts(ev.name)]
            elif line.name == trace.MODULES_LINE:
                mods += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events]
        evs.sort()
        mods.sort()
        steps = {}
        for ev in evs:
            i = bisect.bisect_right(mods, (ev[0], float("inf"))) - 1
            if i >= 0 and ev[0] < mods[i][1]:
                steps.setdefault(i, []).append(ev)
        step = next((s for s in steps.values() if len(s) == layers), None)
        if step is None:
            return None
        kinds = [hbm_operands(name) for _, _, name in step]
        names = {name.split(" = ")[0] for _, _, name in step}
        if None in kinds or len(names) != layers or any(
                name.split(" = ")[0] not in names for _, _, name in evs):
            return None
        return sum(d for _, d, _ in evs) * 1e-9, kinds
    return None


def read(ctx):
    rows = ctx.records.get("expert_rows_by_layer")
    reads = ctx.records.get("expert_reads_by_layer")
    trace_dir = getattr(ctx, "_trace_dir", None)
    if ctx.trace_summary is None or not rows or trace_dir is None:
        return None
    calls = expert_calls(trace.load(trace_dir), len(rows))
    if calls is None or calls[0] <= 0:
        return None
    kernel_s, kinds = calls
    flops = nbytes = 0.0
    for r, u, (mats, moves) in zip(rows, reads, kinds):
        f, b = lfm2_work.experts_work(ctx.cfg, r, u, mats=mats,
                                      row_moves=moves)
        flops, nbytes = flops + f, nbytes + b
    return 100.0 * least_time_s(flops, nbytes, ctx.peaks) / kernel_s
