"""Device time by program phase, and idle gaps on the device's clock,
from a profiler trace (``.xplane.pb``).

    python3 bench/phases.py TRACE.xplane.pb

prints one JSON object: busy and window time, the device time of each
phase per run of the jitted program the phase ran in (one training step
or one decode call), the clock offset, and the largest idle gaps.

The program tags each device op with its phase, the HLO frontend
attribute ``mach_phase`` (``src/repro/kernels/phase.py``), which the op
events of the trace's ``XLA Ops`` line keep in their HLO text: as
``mach_phase="..."`` on XLA ops and inside ``kernel_metadata`` on a
Pallas kernel.  An op with no tag (a program older than the tags, or an
op XLA made itself, such as a layout copy) has phase ``""``.

Host and device clocks differ by a millisecond or two.  The runs that
both sides name by ``run_id`` bound the difference: the host's
``DoEnqueueProgram`` of a run starts before the device's ``XLA
Modules`` event of that run starts, and its ``CompleteCallbacks``
starts after that event ends.  The offset is the middle of the
interval these bounds leave; with no linked run, or bounds that cross,
it is 0 and ``Clock.linked`` is false.  Each idle gap is named by what
the host thread that drove the window was doing at the gap's middle,
shifted onto the device's clock.

The window, the ops in it and busy time are as ``bench/trace.py``
reduces them (``bench.window`` on the host clock, used as it is), so
busy time here equals ``trace.reduce``'s.  No metric of
``BENCHMARK.json`` reads this module yet.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import json
import math
import os
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

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
class Clock:
    """Host time minus device time of one instant, in ns, and the
    interval the linked runs allow."""
    offset_ns: float
    low_ns: float
    high_ns: float
    pairs: int
    linked: bool


@dataclasses.dataclass(frozen=True)
class Op:
    phase: str
    module: str
    dur_ns: float   # clipped to the window


@dataclasses.dataclass
class Phases:
    window_ns: tuple[float, float]
    busy_ns: float                  # averaged over devices
    devices: int
    ops: list[Op]
    runs: dict[str, int]            # module -> runs that overlap the window
    clock: Clock
    gaps: list[tuple[str, float]]   # (host activity, ns) per idle gap

    @property
    def tagged(self) -> bool:
        return any(o.phase != UNTAGGED for o in self.ops)

    def phase_ns(self, phase: str) -> tuple[float, int]:
        """(device ns, op count) of the ops in ``phase`` (``""``: the
        untagged ones), summed over devices."""
        sel = [o.dur_ns for o in self.ops if o.phase == phase]
        return float(sum(sel)), len(sel)

    def split(self) -> dict:
        """Per phase: device ms and ops per call, a call being one run
        of the jitted program the phase's ops ran in (the most frequent
        if several)."""
        modules: dict[str, collections.Counter] = collections.defaultdict(
            collections.Counter)
        for o in self.ops:
            modules[o.phase][o.module] += 1
        out = {}
        for phase in sorted(modules):
            ns, count = self.phase_ns(phase)
            n = self.runs.get(modules[phase].most_common(1)[0][0], 0) or 1
            out[phase or "untagged"] = {
                "ms": ns * 1e-6 / n / self.devices,
                "ops": count / n / self.devices, "calls": n}
        return out

    def top_gaps(self, n: int = 10) -> list[list]:
        tot, cnt = collections.Counter(), collections.Counter()
        for name, ns in self.gaps:
            tot[name] += ns
            cnt[name] += 1
        return [[f"{k} x{cnt[k]}", v * 1e-9] for k, v in tot.most_common(n)]


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


def reduce(xplane_path: str) -> Phases:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(xplane_path).planes)
    (w0, w1), host_events = trace._host_window(planes)
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
            ops.append(Op(phase_of(e.name), module, t - s))
            intervals.append((s, t))
        merged = trace._union(intervals)
        busy += sum(t - s for s, t in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
        names = trace._activities(
            host_events, [(s + t) / 2 + shift.offset_ns for s, t in idle])
        gaps.extend((n, t - s) for n, (s, t) in zip(names, idle))
    if not devices:
        raise ValueError("no TPU device plane with XLA Ops in the trace")
    return Phases((w0, w1), busy / devices, devices, ops, dict(runs), shift,
                  gaps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    args = ap.parse_args(argv)
    p = reduce(args.xplane)
    c = p.clock
    print(json.dumps({
        "window_s": (p.window_ns[1] - p.window_ns[0]) * 1e-9,
        "busy_s": p.busy_ns * 1e-9, "runs": p.runs, "tagged": p.tagged,
        "phases": p.split(),
        "clock": {"offset_ms": c.offset_ns * 1e-6, "linked": c.linked,
                  "bracket_ms": [x * 1e-6 if math.isfinite(x) else None
                                 for x in (c.low_ns, c.high_ns)],
                  "pairs": c.pairs},
        "idle_gaps": p.top_gaps()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
