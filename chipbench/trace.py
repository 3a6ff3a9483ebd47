"""From a profiler trace (xplane) to intervals on ONE device's clock.

Layout on this machine (looked at by hand, PERF.md section 6): plane
``/device:TPU:<i>`` has the lines ``Steps``, ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (leaf operations, back to back: the device's
occupancy) and ``Async XLA Ops`` (DMA spans that overlap the leaf operations
and sum to several times the wall: never occupancy). ``/host:CPU`` has one
line per host thread; the loop's ``TraceAnnotation``s land on a ``python``
line. All ``start_ns`` share the profile's origin.

Rules that keep ``busy_s <= window_s``: one device plane only (device 0),
one line only (``XLA Ops``), a union of intervals and never a sum, and the
window taken from the same device clock as the busy time.
"""

from __future__ import annotations

import collections
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)* = ")
_OP_LABEL = re.compile(r"^%(\S+) = \(?(\w+\[[\d,]*\])?")
_COLLECTIVES = (r"(all-reduce|all-gather|reduce-scatter|collective-permute"
                r"|all-to-all|collective-broadcast)")
# by the instruction's name, or by its opcode: ``... = f32[8] all-reduce(...``
_COLLECTIVE = re.compile(r"^%" + _COLLECTIVES + r"|[ )]" + _COLLECTIVES
                         + r"(-start|-done)?\(")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'


# ------------------------------------------------------------ interval algebra

def merge(intervals):
    """Union of [start, end) intervals as a sorted list without overlaps."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(intervals):
    return sum(hi - lo for lo, hi in intervals)


def subtract(a, b):
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def intersect(a, b):
    """The part of merged ``a`` that merged ``b`` covers."""
    return subtract(a, subtract(a, b))


# ------------------------------------------------------------------ the trace

def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_planes(profile):
    """Device planes by device index."""
    found = {}
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            found[int(m.group(1))] = plane
    return found


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def op_name(event_name):
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion``; a name that is not
    HLO text is returned whole."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def is_collective(event_name):
    return bool(_COLLECTIVE.search(event_name))


def is_mosaic(event_name):
    return MOSAIC_TARGET in event_name


class Reduced:
    """What the per-layer readers get. Times are nanoseconds on device 0's
    clock; ``ops`` are the leaf operations inside the window as
    ``(name, start, end)``; ``step_ns`` the durations of the step program's
    whole executions inside it."""

    def __init__(self, window, step_ns, ops, host, n_device_planes):
        self.window = window
        self.step_ns = step_ns
        self.ops = ops
        self.host = host
        self.n_device_planes = n_device_planes
        self.window_s = (window[1] - window[0]) / 1e9
        self.busy = merge((s, e) for _, s, e in ops)
        self.busy_s = total(self.busy) / 1e9

    @property
    def steps(self):
        return len(self.step_ns)

    def op_seconds_per_step(self, pred):
        """Device seconds a step spends in leaf operations ``pred`` picks."""
        picked = merge((s, e) for n, s, e in self.ops if pred(n))
        return total(picked) / 1e9 / self.steps

    def exposed_seconds_per_step(self, pred):
        """As above, less what other leaf operations cover at the same time."""
        picked = merge((s, e) for n, s, e in self.ops if pred(n))
        others = merge((s, e) for n, s, e in self.ops if not pred(n))
        return total(subtract(picked, others)) / 1e9 / self.steps

    def top_ops(self, n=10):
        per = collections.Counter()
        for name, s, e in self.ops:
            m = _OP_LABEL.match(name)
            label = " ".join(g for g in m.groups() if g) if m else name
            per[label[:120]] += (e - s) / 1e9
        return [[k, v] for k, v in per.most_common(n)]

    def idle_gaps(self, n=10, prefix="chipbench:"):
        """Idle seconds of device 0 inside the window, by what the host's
        loop was doing at the time (its ``TraceAnnotation``s)."""
        gaps = subtract([self.window], self.busy)
        named = collections.defaultdict(list)
        for name, s, e in self.host:
            if name.startswith(prefix):
                named["host: " + name[len(prefix):]].append((s, e))
        per = {k: total(intersect(gaps, merge(v))) / 1e9
               for k, v in named.items()}
        per["host: outside the loop's annotations"] = \
            total(gaps) / 1e9 - sum(per.values())
        return [[k, v] for k, v in sorted(per.items(), key=lambda kv: -kv[1])
                if v > 0][:n]


def reduce(profile):
    """Reduce one traced window. The step program is the module with the most
    device time; its first execution in the trace is dropped (the profiler
    starts in the middle of it) and the window runs from the start of the
    next to the end of the last."""
    planes = device_planes(profile)
    if not planes:
        raise ValueError("trace has no /device:TPU:<i> plane")
    plane = planes[min(planes)]
    modules = _line(plane, "XLA Modules")
    ops_line = _line(plane, "XLA Ops")
    if modules is None or ops_line is None:
        raise ValueError("device plane lacks 'XLA Modules' or 'XLA Ops'")
    per = collections.defaultdict(list)
    for ev in modules.events:
        per[ev.name.split("(", 1)[0]].append((ev.start_ns, ev.end_ns))
    if not per:
        raise ValueError("no program ran on the device while tracing")
    step = max(per.values(), key=lambda v: sum(e - s for s, e in v))
    step = sorted(step)[1:]
    if len(step) < 2:
        raise ValueError("fewer than two whole steps in the trace")
    window = (step[0][0], step[-1][1])
    ops = [(ev.name, max(ev.start_ns, window[0]), min(ev.end_ns, window[1]))
           for ev in ops_line.events
           if ev.end_ns > window[0] and ev.start_ns < window[1]]
    host = []
    for p in profile.planes:
        if p.name == "/host:CPU":
            for line in p.lines:
                if line.name.startswith("python"):
                    host += [(ev.name, ev.start_ns, ev.end_ns)
                             for ev in line.events]
    return Reduced(window, [e - s for s, e in step], ops, host, len(planes))
