"""Reduce a ``jax.profiler`` trace to device busy time, kernel time and
labelled idle gaps.

The loader reads the ``.xplane.pb`` that ``jax.profiler.stop_trace``
writes, with nothing but ``jax.profiler.ProfileData``: device operations
are the events of each device plane's ``XLA Ops`` line; host spans are
the benchmark's own ``TraceAnnotation`` events, named ``chipbench.*``.
The arithmetic below works on plain ``(start_ns, end_ns)`` intervals, so
the tests can check it on a trace they build.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

#: prefix of every host span the benchmark writes
SPAN = "chipbench."
#: the span around the traced window
WINDOW = SPAN + "window"
#: device planes, and the line of each that holds its operations
DEVICE_PLANE = "/device:"
OP_LINE = "XLA Ops"
#: stats whose values name a device operation beside its event name
_NAME_STATS = ("long_name", "hlo_op", "hlo_module", "tf_op", "name")

Interval = Tuple[int, int]


@dataclasses.dataclass
class DeviceOp:
    device: str
    name: str          # event name plus the naming stats, space-separated
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    devices: List[str]
    ops: List[DeviceOp]
    spans: List[Tuple[str, int, int]]   # (name, start_ns, end_ns)
    window: Interval


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(intervals: Sequence[Interval], window: Interval) -> int:
    """Length of the union of ``intervals`` inside ``window``."""
    return sum(e - s for s, e in merge(clip(intervals, window)))


def gaps(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """The stretches of ``window`` that no interval covers."""
    out, t = [], window[0]
    for s, e in merge(clip(intervals, window)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def label(gap: Interval, spans: Sequence[Tuple[str, int, int]]) -> str:
    """The innermost host span that covers the middle of ``gap``."""
    mid = (gap[0] + gap[1]) // 2
    best: Optional[Tuple[int, str]] = None
    for name, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1][len(SPAN):] if best else "outside every span"


def device_busy(trace: Trace) -> Tuple[float, Dict[str, int]]:
    """Mean busy seconds over the devices inside the window, and each
    device's busy nanoseconds."""
    per = {d: busy_ns([(o.start, o.end) for o in trace.ops
                       if o.device == d], trace.window)
           for d in trace.devices}
    return (sum(per.values()) / max(len(per), 1) / 1e9, per)


def op_seconds(trace: Trace, match=None) -> float:
    """Summed device time of the operations that ``match`` names, inside
    the window, averaged over the devices.  ``match`` is None (every
    operation), a string the operation's name or naming stats contain,
    or a list of token tuples: an operation matches when it contains
    every token of one tuple (a kernel by its own name, or the custom
    call inside its program's module)."""
    if isinstance(match, str):
        match = [(match,)]
    total = 0
    for o in trace.ops:
        if match is None or any(all(t in o.name for t in alt)
                                for alt in match):
            s, e = max(o.start, trace.window[0]), min(o.end, trace.window[1])
            total += max(0, e - s)
    return total / max(len(trace.devices), 1) / 1e9


def kernel_seconds(trace: Trace, match, what: str) -> float:
    """``op_seconds`` of a kernel that the window launched; finding no
    operation by its names is an error, never a zero or a silence."""
    t = op_seconds(trace, match)
    if t <= 0:
        from chipbench.cells import BenchError
        seen = sorted({o.name[:160] for o in trace.ops})[:40]
        raise BenchError(f"{what}: the window launched it, but no device "
                         f"operation of the trace matches {match}; "
                         f"operations seen: {seen}")
    return t


def breakdown(trace: Trace, top: int = 10) -> Dict[str, List]:
    """The device operations that took most time (by event name, summed
    over the devices), and the longest idle gaps of the first device,
    each labelled with the host span it fell in."""
    per: Dict[str, int] = {}
    for o in trace.ops:
        s, e = max(o.start, trace.window[0]), min(o.end, trace.window[1])
        if e > s:
            key = o.name.split(" ", 1)[0]
            per[key] = per.get(key, 0) + e - s
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    idle: List[Tuple[str, float]] = []
    if trace.devices:
        d0 = trace.devices[0]
        g = gaps([(o.start, o.end) for o in trace.ops if o.device == d0],
                 trace.window)
        g.sort(key=lambda iv: iv[0] - iv[1])
        idle = [[label(iv, trace.spans), (iv[1] - iv[0]) / 1e9]
                for iv in g[:top]]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": idle}


def _stat_names(event) -> str:
    vals = []
    for stat in getattr(event, "stats", ()):
        try:
            key, val = stat
        except (TypeError, ValueError):
            continue
        if key in _NAME_STATS and isinstance(val, str):
            vals.append(val)
    return " ".join(vals)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``: the device planes' operations and the
    benchmark's host spans, with the window from the ``WINDOW`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, ops, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = [ln for ln in plane.lines if ln.name == OP_LINE]
            if not lines:
                continue
            devices.append(plane.name)
            for ln in lines:
                for ev in ln.events:
                    s = int(ev.start_ns)
                    ops.append(DeviceOp(
                        plane.name, f"{ev.name} {_stat_names(ev)}".strip(),
                        s, s + int(ev.duration_ns)))
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if not win:
        raise ValueError(f"{path}: no {WINDOW} span in the trace")
    return Trace(sorted(devices), ops, spans, win[0])
