"""Reduction of a ``jax.profiler`` trace to the numbers the readers use.

Reads the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes, with
nothing but ``jax.profiler.ProfileData``. What a TPU v5e trace holds (seen
by hand on the chip before this was written):

- each chip is a plane ``/device:TPU:<n>``. Its ``XLA Modules`` line has one
  event per execution of a compiled program; its ``XLA Ops`` line one event
  per HLO operation, named by the instruction's text (``%fusion.3 = ...``),
  and nested: a ``while`` loop's event spans the operations of its body. A
  Pallas kernel is an operation whose text holds ``tpu_custom_call``;
- the host's plane ``/host:CPU`` has one line per thread. The harness's
  ``bench.*`` annotations are on the Python thread, and each program launch
  is a ``PJRT_LoadedExecutable_Execute`` event;
- the device's clock is not the host's: their offset differs from trace to
  trace by a millisecond or more. So device work is tied to host spans by
  order, not by time: the n-th launch on the host is the n-th module on the
  device.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"
SPAN_PREFIX = "bench."
KERNEL = "tpu_custom_call"


def load(trace_dir) -> "object":
    """The newest trace under ``trace_dir``."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_file(files[-1])


def load_file(path) -> "object":
    import jax
    return jax.profiler.ProfileData.from_file(str(path))


def union(intervals) -> list:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi, ends=None) -> float:
    """Length of ``merged`` (disjoint, sorted) inside [lo, hi). ``ends``,
    the list of the intervals' ends, makes the lookup logarithmic."""
    ends = ends if ends is not None else [e for _, e in merged]
    total, i = 0.0, bisect.bisect_right(ends, lo)
    while i < len(merged) and merged[i][0] < hi:
        total += min(merged[i][1], hi) - max(merged[i][0], lo)
        i += 1
    return total


def leaves(events) -> list:
    """The (name, start, end) events that contain no other event: a
    ``while`` or ``call`` that spans its body's operations is left out.
    Events that only overlap are both kept."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    parent = [False] * len(evs)
    stack = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(evs, parent) if not p]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float          # mean over the chips used
    ops: list              # leaf operations (name, start_ns, end_ns, device)
    busy: dict             # device -> merged busy intervals (ns)
    modules: dict          # device -> sorted (start_ns, end_ns) of programs
    launches: list         # sorted host start_ns of program launches
    spans: list            # sorted (name, start_ns, end_ns) host spans

    def top_ops(self, n: int) -> list:
        """The leaf operations that took the most device time, all chips."""
        tot = collections.defaultdict(float)
        for name, s, e, _ in self.ops:
            tot[name.split(" = ")[0]] += (e - s) * 1e-9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def op_seconds(self, match) -> float:
        """Device seconds of the leaf operations for which ``match(name)``
        holds, all chips."""
        return sum(e - s for name, s, e, _ in self.ops if match(name)) * 1e-9

    def span_module_s(self, device: int = 0):
        """Device seconds of the programs launched inside each host span,
        by span name; None where launches and modules do not pair up."""
        mods = self.modules.get(device, [])
        if not mods or len(mods) != len(self.launches):
            return None
        starts = [sp[1] for sp in self.spans]
        out = collections.defaultdict(float)
        for t, (s, e) in zip(self.launches, mods):
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and self.spans[i][2] > t:
                out[self.spans[i][0]] += (e - s) * 1e-9
        return dict(out)

    def skew_ns(self, device: int = 0) -> float:
        """Device clock minus host clock, from the launch nearest to its
        module (0 where they do not pair up)."""
        mods = self.modules.get(device, [])
        if not mods or len(mods) != len(self.launches):
            return 0.0
        return min(m[0] - t for t, m in zip(self.launches, mods))

    def top_gaps(self, n: int, device: int = 0) -> list:
        """Idle time between device operations, by the host span that was
        open in the middle of each gap (the harness's spans do not nest)."""
        merged, skew = self.busy.get(device, []), self.skew_ns(device)
        starts = [sp[1] for sp in self.spans]
        out = collections.defaultdict(float)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            mid = (e0 + s1) / 2 - skew
            i = bisect.bisect_right(starts, mid) - 1
            name = self.spans[i][0] if i >= 0 and self.spans[i][2] > mid \
                else "outside bench spans"
            out[name] += (s1 - e0) * 1e-9
        return [[k, v] for k, v in sorted(out.items(),
                                          key=lambda kv: -kv[1])[:n]]


def summarize(pd, window_s: float, chips: int) -> Summary:
    ops, per_dev, modules = [], collections.defaultdict(list), {}
    launches, spans = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                if line.name == OPS_LINE:
                    per_dev[dev].extend((s, e) for _, s, e in evs)
                    ops.extend((n, s, e, dev) for n, s, e in leaves(evs))
                elif line.name == MODULES_LINE:
                    modules[dev] = sorted((s, e) for _, s, e in evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif ev.name == LAUNCH:
                        launches.append(ev.start_ns)
    busy = {d: union(iv) for d, iv in per_dev.items()}
    used = sorted(busy)[:chips]
    busy_s = (sum(sum(e - s for s, e in busy[d]) for d in used)
              / max(len(used), 1)) * 1e-9
    return Summary(window_s=window_s, busy_s=busy_s, ops=ops, busy=busy,
                   modules=modules, launches=sorted(launches),
                   spans=sorted(spans, key=lambda x: x[1]))
