"""Reduction of a profiler trace to the device's busy time, its idle share
and a breakdown of where the time went.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the traced window and averaged over the devices; the
idle share is 1 - busy / window. Each idle gap is named after the
innermost host span that was open at its middle, so a gap reads as what
the host was doing meanwhile.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

# Lines of a device plane that hold one event per executed operation.
DEVICE_OP_LINES = ('XLA Ops',)
WINDOW_SPAN = 'bench.window'


@dataclasses.dataclass
class Event:
    start: float       # seconds
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    devices: dict      # plane name -> [Event] of device operations
    host: list         # [Event] of host spans on the thread that ran the
                       # window (the one holding the WINDOW_SPAN span)


def short_name(hlo: str) -> str:
    """An HLO operation by its name and result type, the text of its
    operands dropped: '%fusion.12 = f32[8]{0} fusion(...), kind=kLoop'
    -> 'fusion.12 f32[8]{0} kLoop'."""
    name, _, rest = hlo.partition(' = ')
    out = [name.lstrip('%'), rest.split(' ', 1)[0]]
    if ', kind=' in rest:
        out.append(rest.split(', kind=', 1)[1].split(',', 1)[0])
    return ' '.join(x for x in out if x)[:160]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, '**', '*.xplane.pb'),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {log_dir}')
    return found[-1]


def load(path: str) -> Trace:
    """Device operations and host spans of one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith('/device:'):
            evs = [Event(e.start_ns * 1e-9, e.end_ns * 1e-9,
                         short_name(e.name))
                   for line in plane.lines if line.name in DEVICE_OP_LINES
                   for e in line.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                evs = [Event(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                       for e in line.events if e.duration_ns > 0]
                if any(e.name == WINDOW_SPAN for e in evs):
                    host = evs
    return Trace(devices, host)


def merge(events, lo: float, hi: float) -> list:
    """Union of the events' intervals clipped to [lo, hi], sorted."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_bounds(trace: Trace) -> tuple:
    """The traced window: the host span named WINDOW_SPAN."""
    spans = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f'the trace holds no {WINDOW_SPAN!r} span')
    return spans[0].start, spans[0].end


def self_times(events) -> dict:
    """Seconds per operation name, each event less the events nested in it
    (a loop or conditional holds the operations of its body)."""
    out = collections.Counter()
    stack = []                    # [end, name, start, child seconds]
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= e.start:
            end, name, start, child = stack.pop()
            out[name] += (end - start) - child
            if stack:
                stack[-1][3] += end - start
        stack.append([e.end, e.name, e.start, 0.0])
    while stack:
        end, name, start, child = stack.pop()
        out[name] += (end - start) - child
        if stack:
            stack[-1][3] += end - start
    return out


def _host_label(host: list, t: float) -> str:
    inside = [e for e in host if e.start <= t <= e.end
              and e.name != WINDOW_SPAN]
    if not inside:
        return 'host idle'
    return min(inside, key=lambda e: e.end - e.start).name


def reduce(trace: Trace, top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, idle_pct, and the breakdown:
    the `top` device operations by self seconds and the `top` longest
    idle gaps, named by the host span open at their middle."""
    lo, hi = window_bounds(trace)
    window_s = hi - lo
    if not trace.devices or window_s <= 0:
        return {'busy_s': 0.0, 'window_s': window_s, 'idle_pct': None,
                'breakdown': {'device_ops': [], 'idle_gaps': []}}
    busy, ops, gaps = [], collections.Counter(), []
    for evs in trace.devices.values():
        merged = merge(evs, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        ops.update(self_times(
            Event(max(e.start, lo), min(e.end, hi), e.name) for e in evs
            if e.end > lo and e.start < hi))
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    busy_s = sum(busy) / len(busy)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        'busy_s': busy_s, 'window_s': window_s,
        'idle_pct': 100.0 * (1.0 - busy_s / window_s),
        'breakdown': {
            'device_ops': [[n, s] for n, s in ops.most_common(top)],
            'idle_gaps': [[_host_label(trace.host, 0.5 * (s + e)), e - s]
                          for s, e in gaps[:top]]}}
