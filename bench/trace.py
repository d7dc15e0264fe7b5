"""Reduction of a profiler trace to the device's busy time and idle gaps.

``device_intervals`` takes the device operations out of a trace read with
``jax.profiler.ProfileData``: on a TPU the events of each ``/device:TPU:k``
plane's ``XLA Ops`` line, on the CPU (rehearsals) the XLA operations that
the host client's worker threads record. ``host_spans`` takes the host's
named events. ``reduce`` then works on plain ``(start_ns, end_ns)``
intervals, clipped to the traced window:

* busy time is the length of the union of the device intervals, averaged
  over the devices seen;
* an idle gap is a stretch of the window that no device interval covers;
  each gap is named by the innermost host span that covers its midpoint;
* ``breakdown`` lists the device operations that took most time, by the
  names XLA gave them, and the idle seconds summed by host span.
"""

from __future__ import annotations

import heapq
from collections import defaultdict

TOP = 10
_CPU_CLIENT = "tf_XLAPjRtCpuClient"
_CPU_SKIP = ("ThreadpoolListener::", "ThunkExecutor::", "end: ")
NO_SPAN = "(no host span)"


def device_intervals(planes, platform: str) -> dict:
    """{device: [(start_ns, end_ns, op_name), ...]} from trace planes."""
    out = defaultdict(list)
    for plane in planes:
        if platform == "cpu":
            if plane.name != "/host:CPU":
                continue
            lines = [ln for ln in plane.lines
                     if ln.name.startswith(_CPU_CLIENT)]
            dev = "cpu"
        else:
            if not plane.name.startswith("/device:"):
                continue
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            dev = plane.name
        for line in lines:
            for ev in line.events:
                if ev.name.startswith(_CPU_SKIP) or ev.duration_ns <= 0:
                    continue
                out[dev].append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    return dict(out)


def host_spans(planes) -> list:
    """[(start_ns, end_ns, name), ...]: the host's named events, every
    thread of the ``/host:CPU`` plane but the XLA client's own."""
    out = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith(_CPU_CLIENT):
                continue
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def union(intervals, lo: float, hi: float) -> list:
    """Merged, sorted intervals clipped to [lo, hi]."""
    merged = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def gaps(busy: list, lo: float, hi: float) -> list:
    """The stretches of [lo, hi] that the merged ``busy`` leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans, times) -> list:
    """For each time, the name of the shortest span that covers it: one
    sweep over the spans by start, with the started ones in a heap by
    length, from which those already ended are dropped as they surface."""
    order = sorted(range(len(times)), key=times.__getitem__)
    spans = sorted(spans)
    out = [NO_SPAN] * len(times)
    heap, k = [], 0
    for i in order:
        t = times[i]
        while k < len(spans) and spans[k][0] <= t:
            s, e, name = spans[k]
            heapq.heappush(heap, (e - s, e, name))
            k += 1
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        if heap:
            out[i] = heap[0][2]
    return out


def reduce(devices: dict, spans: list, lo: float, hi: float) -> dict:
    """Busy and window seconds, and the breakdown, over the window
    [lo, hi] in nanoseconds. ``devices`` is ``device_intervals``' result."""
    window_s = (hi - lo) / 1e9
    busy_total = 0.0
    op_time = defaultdict(float)
    idle = defaultdict(float)
    for ivs in devices.values():
        busy = union(ivs, lo, hi)
        busy_total += sum(e - s for s, e in busy) / 1e9
        for s, e, name in ivs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] += d / 1e9
        free = gaps(busy, lo, hi)
        names = innermost(spans, [(s + e) / 2 for s, e in free])
        for (s, e), name in zip(free, names):
            idle[name] += (e - s) / 1e9
    ndev = max(len(devices), 1)
    top = lambda d: [[k, v / ndev] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": busy_total / ndev, "window_s": window_s,
            "breakdown": {"device_ops": top(op_time),
                          "idle_gaps": top(idle)}}


def window_of(spans, name: str):
    """(start_ns, end_ns) of the host span ``name``; the last if several."""
    hits = [(s, e) for s, e, n in spans if n == name]
    if not hits:
        raise ValueError(f"the trace holds no span {name!r}")
    return hits[-1]


def reduce_file(path: str, platform: str, window: str) -> dict:
    """Read one ``.xplane.pb`` and reduce it over the span ``window``."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    spans = host_spans(planes)
    lo, hi = window_of(spans, window)
    inner = [sp for sp in spans if sp[2] != window]
    return reduce(device_intervals(planes, platform), inner, lo, hi)
