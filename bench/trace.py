"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

From each TPU device plane (``/device:TPU:<n>``):

* the operations of its ``XLA Ops`` line, each with the jitted program
  (``XLA Modules`` line) it ran in, whether it is a Pallas kernel (a
  ``tpu_custom_call``), and its program phase;
* the runs of each jitted program that overlap the window;
* busy time: the union of those operations' intervals inside the
  window, averaged over the devices.

The program tags each device op with its phase, the HLO frontend
attribute ``mach_phase`` (``src/repro/kernels/phase.py``), which the op
events of the ``XLA Ops`` line keep in their HLO text: as
``mach_phase="..."`` on XLA ops and inside ``kernel_metadata`` on a
Pallas kernel.  An op with no tag (a program older than the tags, or an
op XLA made itself, such as a layout copy) has phase ``""``.

From the host plane, the window is the span of the ``bench.window``
annotation that the harness wraps round the measured loop (on the
host's clock, used as it is), and every idle gap of the device inside
it is named by what the host thread that drove the loop was doing at
the gap's middle, moved onto the host's clock by the offset below (its
innermost ``bench.*`` annotation and its innermost event).

Host and device clocks differ by a millisecond or two.  The runs that
both sides name by ``run_id`` bound the difference: the host's
``DoEnqueueProgram`` of a run starts before the device's ``XLA
Modules`` event of that run starts, and its ``CompleteCallbacks``
starts after that event ends.  The offset is the middle of the
interval these bounds leave; with no linked run, or bounds that cross,
it is 0 and ``Clock.linked`` is false.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
KEY = "mach_phase"
UNTAGGED = ""
_KERNEL_TAG = re.compile(r'\\?"' + KEY + r'\\?"\s*:\s*\\?"([^"\\]+)')
_XLA_TAG = re.compile(r"\b" + KEY + r'="([^"]+)"')
ENQUEUE, CALLBACKS = "DoEnqueueProgram", "CompleteCallbacks"


def phase_of(text: str) -> str:
    """The phase in an op event's HLO text; a kernel's own tag
    (``kernel_metadata``) wins over the XLA one around it."""
    i = text.find("frontend_attributes={")
    if i < 0:
        return UNTAGGED
    m = _KERNEL_TAG.search(text, i) or _XLA_TAG.search(text, i)
    return m.group(1) if m else UNTAGGED


@dataclasses.dataclass(frozen=True)
class Op:
    name: str          # the HLO instruction, up to " = "
    module: str        # the jitted program, without its fingerprint
    start_ns: float
    dur_ns: float      # clipped to the window
    kernel: bool       # a Pallas (Mosaic) kernel
    phase: str         # its mach_phase tag, "" where it has none


@dataclasses.dataclass(frozen=True)
class Clock:
    """Host time minus device time of one instant, in ns, and the
    interval the linked runs allow."""
    offset_ns: float
    low_ns: float
    high_ns: float
    pairs: int
    linked: bool


UNLINKED = Clock(0.0, float("-inf"), float("inf"), 0, False)


@dataclasses.dataclass
class Summary:
    window_ns: tuple[float, float]
    busy_ns: float                 # averaged over devices
    devices: int
    ops: list[Op]                  # every device's ops inside the window
    gaps: list[tuple[str, float]]  # (host activity, ns) per idle gap
    runs: dict[str, int]           # module -> its runs overlapping the
                                   # window, summed over devices
    clock: Clock = UNLINKED

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    @property
    def tagged(self) -> bool:
        """Whether the program tags its ops with phases at all."""
        return any(o.phase != UNTAGGED for o in self.ops)

    def kernel_ns(self, modules: tuple[str, ...]) -> tuple[float, int]:
        """(total device ns, count) of the Pallas kernels that ran inside
        the jitted programs named ``modules``."""
        sel = [o.dur_ns for o in self.ops if o.kernel and o.module in modules]
        return float(sum(sel)), len(sel)

    def phase_ns(self, phase: str, modules: tuple[str, ...] | None = None
                 ) -> tuple[float, int]:
        """(device ns, op count) of the ops in ``phase`` (``""``: the
        untagged ones) inside the programs ``modules`` (None: any),
        summed over devices."""
        sel = [o.dur_ns for o in self.ops if o.phase == phase
               and (modules is None or o.module in modules)]
        return float(sum(sel)), len(sel)

    def _calls(self, phase: str, modules: tuple[str, ...] | None) -> int:
        """The runs of the program the phase's ops ran in (the most
        frequent if several; with no such op, the most run of
        ``modules``)."""
        counts = collections.Counter(
            o.module for o in self.ops if o.phase == phase
            and (modules is None or o.module in modules))
        if counts:
            return self.runs.get(counts.most_common(1)[0][0], 0)
        return max((self.runs.get(m, 0) for m in modules or ()), default=0)

    def phase_ms(self, phase: str, modules: tuple[str, ...]
                 ) -> float | None:
        """Device ms of the ops in ``phase`` inside ``modules`` per run
        of the program they ran in (one training step or one decode
        call), averaged over devices; None where none of ``modules``
        ran in the window."""
        calls = self._calls(phase, modules)
        if not calls:
            return None
        return self.phase_ns(phase, modules)[0] * 1e-6 / calls

    def split(self) -> dict:
        """Per phase: device ms and ops per call, as ``phase_ms``."""
        out = {}
        for phase in sorted({o.phase for o in self.ops}):
            ns, count = self.phase_ns(phase)
            n = self._calls(phase, None) or 1
            out[phase or "untagged"] = {"ms": ns * 1e-6 / n,
                                        "ops": count / n, "calls": n}
        return out

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


def _stat(event, name):
    for k, v in event.stats:
        if k == name:
            return int(v)
    return None


def clock(planes) -> Clock:
    """Host-minus-device offset from the runs linked by ``run_id``."""
    enqueue, callbacks, modules = {}, {}, []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (ENQUEUE, CALLBACKS):
                        side = enqueue if e.name == ENQUEUE else callbacks
                        run = _stat(e, "run_id")
                        if run is not None:
                            side.setdefault(run, e.start_ns)
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(_stat(e, "run_id"), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
    low, high, pairs = [], [], 0
    for run, start, end in modules:
        if run in enqueue:
            low.append(enqueue[run] - start)
        if run in callbacks:
            high.append(callbacks[run] - end)
        pairs += run in enqueue or run in callbacks
    lo = max(low) if low else float("-inf")
    hi = min(high) if high else float("inf")
    if not (low and high) or lo > hi:
        return Clock(0.0, lo, hi, pairs, False)
    return Clock((lo + hi) / 2, lo, hi, pairs, True)


def reduce(xplane_path: str) -> Summary:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(xplane_path).planes)
    (w0, w1), host_events = _host_window(planes)
    shift = clock(planes)
    ops: list[Op] = []
    runs: collections.Counter = collections.Counter()
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
        runs.update(name for s, e, name in modules if s < w1 and e > w0)
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
                          e.start_ns, t - s, CUSTOM_CALL in e.name,
                          phase_of(e.name)))
            intervals.append((s, t))
        merged = _union(intervals)
        busy += sum(t - s for s, t in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
        names = _activities(
            host_events, [(s + t) / 2 + shift.offset_ns for s, t in idle])
        gaps.extend((n, t - s) for n, (s, t) in zip(names, idle))
    if not devices:
        raise ValueError("no TPU device plane with XLA Ops in the trace")
    return Summary((w0, w1), busy / devices, devices, ops, gaps, dict(runs),
                   shift)
