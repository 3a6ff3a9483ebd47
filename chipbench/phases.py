"""Device time a step by phase of the step program, from a profiler trace.

    python3 -m chipbench.phases <file.xplane.pb>

The library names its device program (``jax.named_scope``): ``hvd_forward``
round the model and the loss, which JAX writes as ``jvp(hvd_forward)`` on
forward operations and ``transpose(jvp(hvd_forward))`` on backward ones, with
``rematted_computation`` on remat's recompute inside the backward;
``hvd_exchange_pack``/``_reduce``/``_unpack`` in the gradient exchange;
``hvd_optimizer`` round the update. The scope reaches the profile as the stat
``tf_op`` of an event's METADATA (the HLO ``op_name``), not in the event's
name, and ``jax.profiler.ProfileData`` shows an event's own stats only. So
this file reads the xplane's wire format itself (the few fields it needs, of
device 0's plane only) into objects shaped like ``ProfileData``'s, each event
with its ``scope``.

Interval rules are ``chipbench/trace.py``'s: one plane (device 0), one line
(``XLA Ops``), the window of ``trace.reduce``, and a union of intervals per
phase. Leaf operations run one at a time, so the phases sum to the busy time.
A fusion is classed by the scope its event carries, which is its root's:
operations fused into a consumer of another phase count with the consumer.

The harness does not call this: ``Reduced.ops`` keeps names only (PERF.md
section 7 says what a ``benchmark`` issue has to change for per-phase readers).
"""

from __future__ import annotations

import json
import struct
import sys
import types

from chipbench import trace

SCOPE_STAT = "tf_op"
# first match wins: the exchange and the optimizer are called from outside
# ``hvd_forward``; recompute and backward both carry ``transpose(jvp(...))``
PHASES = (
    ("exchange_pack", "hvd_exchange_pack"),
    ("exchange_reduce", "hvd_exchange_reduce"),
    ("exchange_unpack", "hvd_exchange_unpack"),
    ("optimizer", "hvd_optimizer"),
    ("remat", "rematted_computation"),
    ("backward", "transpose(jvp(hvd_forward))"),
    ("forward", "jvp(hvd_forward)"),
)
OTHER = "other"


def phase_of(scope):
    for phase, marker in PHASES:
        if marker in scope:
            return phase
    return OTHER


# ------------------------------------------------------------ the wire format

def _varint(buf, i):
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the payload for a length-delimited field, raw bytes for fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"xplane: wire type {kind} at byte {i}")
            value = buf[i:i + size]
            i += size
        yield number, value


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(buf, stat_names):
    """One XStat as ``(name, value)``; a ``ref_value`` is looked up."""
    name = value = None
    for number, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number in (3, 4):
            value = v
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf):
    """XPlane: name = 2, lines = 3, event_metadata = 4, stat_metadata = 5."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_meta.__setitem__(*_map_entry(v))
        elif number == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, x in _fields(meta) if n == 2), "")
    names = {}       # XEventMetadata: name = 2, stats = 5
    for key, meta in event_meta.items():
        label, scope = "", ""
        for number, v in _fields(meta):
            if number == 2:
                label = _text(v)
            elif number == 5:
                stat, value = _stat(v, stat_names)
                if stat == SCOPE_STAT:
                    scope = value
        names[key] = (label, scope)
    return types.SimpleNamespace(
        name=name, lines=[_line(v, names) for v in lines])


def _line(buf, names):
    """XLine: name = 2, timestamp_ns = 3, events = 4; XEvent: metadata_id =
    1, offset_ps = 2, duration_ps = 3. Times as ``ProfileData`` gives them."""
    name, t0, raw = "", 0, []
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            t0 = v
        elif number == 4:
            raw.append(v)
    events = []
    for ev in raw:
        meta = offset = duration = 0
        for number, v in _fields(ev):
            if number == 1:
                meta = v
            elif number == 2:
                offset = v
            elif number == 3:
                duration = v
        label, scope = names.get(meta, ("", ""))
        start = float(t0 + offset // 1000)      # whole ns, as ProfileData
        events.append(types.SimpleNamespace(
            name=label, scope=scope, start_ns=start,
            end_ns=start + duration // 1000))
    return types.SimpleNamespace(name=name, events=events)


def parse(serialized):
    """A serialized XSpace as a profile of its lowest device plane alone
    (other planes, the host's megabytes of events among them, are skipped
    unread)."""
    buf = memoryview(serialized)
    found = {}
    for number, v in _fields(buf):
        if number == 1:      # XSpace.planes
            name = next((_text(x) for n, x in _fields(v) if n == 2), "")
            m = trace._DEVICE_PLANE.match(name)
            if m:
                found[int(m.group(1))] = v
    if not found:
        raise ValueError("trace has no /device:TPU:<i> plane")
    return types.SimpleNamespace(planes=[_plane(found[min(found)])])


def load(path):
    with open(path, "rb") as f:
        return parse(f.read())


# ------------------------------------------------------------------ the phases

def by_phase(profile):
    """``{phase: device seconds a step}`` over ``trace.reduce``'s window,
    every phase of ``PHASES`` and ``other`` present, plus ``busy`` (their
    sum: the union of all leaf operations) for the caller to check against."""
    reduced = trace.reduce(profile)
    lo, hi = reduced.window
    ops = trace._line(profile.planes[0], "XLA Ops").events
    picked = {phase: [] for phase, _ in PHASES}
    picked[OTHER] = []
    for ev in ops:
        if ev.end_ns > lo and ev.start_ns < hi:
            picked[phase_of(ev.scope)].append(
                (max(ev.start_ns, lo), min(ev.end_ns, hi)))
    out = {phase: trace.total(trace.merge(spans)) / 1e9 / reduced.steps
           for phase, spans in picked.items()}
    out["busy"] = reduced.busy_s / reduced.steps
    return out


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit(__doc__)
    phases = by_phase(load(argv[0]))
    busy = phases.pop("busy")
    print(json.dumps({
        "ms_a_step": {k: 1e3 * v for k, v in phases.items()},
        "busy_ms_a_step": 1e3 * busy,
        "sum_over_busy": sum(phases.values()) / busy,
        "other_share": phases[OTHER] / busy}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
