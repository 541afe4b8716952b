"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

From each TPU device plane (``/device:TPU:<n>``):

* the operations of its ``XLA Ops`` line, each with the jitted program
  (``XLA Modules`` line) it ran in, and whether it is a Pallas kernel
  (a ``tpu_custom_call``);
* busy time: the union of those operations' intervals inside the
  window, averaged over the devices.

From the host plane, the window is the span of the ``bench.window``
annotation that the harness wraps round the measured loop, and every
idle gap of the device inside it is named by what the host thread that
drove the loop was doing at the gap's middle (its innermost ``bench.*``
annotation and its innermost event).  Host and device clocks agree to
about a millisecond, so a gap shorter than that is named roughly.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

WINDOW = "bench.window"
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Op:
    name: str          # the HLO instruction, up to " = "
    module: str        # the jitted program, without its fingerprint
    start_ns: float
    dur_ns: float
    kernel: bool       # a Pallas (Mosaic) kernel


@dataclasses.dataclass
class Summary:
    window_ns: tuple[float, float]
    busy_ns: float                 # averaged over devices
    devices: int
    ops: list[Op]                  # every device's ops inside the window
    gaps: list[tuple[str, float]]  # (host activity, ns) per idle gap

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def kernel_ns(self, modules: tuple[str, ...]) -> tuple[float, int]:
        """(total device ns, count) of the Pallas kernels that ran inside
        the jitted programs named ``modules``."""
        sel = [o.dur_ns for o in self.ops if o.kernel and o.module in modules]
        return float(sum(sel)), len(sel)

    def top_ops(self, n: int = 10) -> list[list]:
        tot = collections.Counter()
        for o in self.ops:
            tot[f"{o.module}:{o.name}"] += o.dur_ns
        return [[k, v * 1e-9] for k, v in tot.most_common(n)]

    def top_gaps(self, n: int = 10) -> list[list]:
        tot, cnt = collections.Counter(), collections.Counter()
        for name, ns in self.gaps:
            tot[name] += ns
            cnt[name] += 1
        return [[f"{k} x{cnt[k]}", v * 1e-9] for k, v in tot.most_common(n)]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir},"
                                f" found {len(paths)}")
    return paths[0]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_window(planes) -> tuple[tuple[float, float], list]:
    """The bench.window span and the events of the thread it is on,
    sorted by start."""
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events)
            spans = [(s, e) for s, e, n in events if n == WINDOW]
            if spans:
                return spans[-1], events
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def _activities(events, points: list[float]) -> list[str]:
    """For each point (ascending), the innermost bench.* annotation and
    innermost event covering it, by one sweep over the thread's nested
    events."""
    out, stack, i = [], [], 0
    for t in points:
        while i < len(events) and events[i][0] <= t:
            while stack and stack[-1][1] < events[i][0]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        if not stack:
            out.append("host idle")
            continue
        inner = stack[-1][2]
        bench = next((e[2] for e in reversed(stack)
                      if e[2].startswith("bench.")), "")
        out.append(inner if not bench or bench == inner
                   else f"{bench}/{inner}")
    return out


def reduce(xplane_path: str) -> Summary:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(xplane_path).planes)
    (w0, w1), host_events = _host_window(planes)
    ops: list[Op] = []
    busy, devices = 0.0, 0
    gaps: list[tuple[str, float]] = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        devices += 1
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                          e.name.split("(")[0])
                         for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in modules]
        intervals = []
        for e in lines["XLA Ops"]:
            s, t = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if t <= s:
                continue
            j = bisect.bisect_right(starts, e.start_ns) - 1
            module = modules[j][2] if j >= 0 and e.start_ns <= modules[j][1] \
                else ""
            ops.append(Op(e.name.split(" = ")[0].lstrip("%"), module,
                          e.start_ns, t - s, CUSTOM_CALL in e.name))
            intervals.append((s, t))
        merged = _union(intervals)
        busy += sum(t - s for s, t in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
        names = _activities(host_events, [(s + t) / 2 for s, t in idle])
        gaps.extend((n, t - s) for n, (s, t) in zip(names, idle))
    if not devices:
        raise ValueError("no TPU device plane with XLA Ops in the trace")
    return Summary((w0, w1), busy / devices, devices, ops, gaps)
