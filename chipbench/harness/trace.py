"""Reduction of a profiler trace to device busy time, top operations and
idle gaps labelled by what the host was doing.

Inputs are plain tuples so the reduction can be checked on a small trace kept
with the tests:

  device ops   (name, start_ns, end_ns), every operation the device ran;
  host spans   (name, start_ns, end_ns), the program's spans on the same
               clock (the benchmark aligns them through one marker);
  window       (start_ns, end_ns) of the traced jobs.

Busy time is the union of the op intervals inside the window. An idle gap is
a stretch of the window with no op running, cut where the host's activity
changes; the host's activity at an instant is the open span that started
last (threads overlap; the newest span is the work most recently begun), or
"none". Gaps are reported longest first.
"""
from __future__ import annotations

import glob
import heapq
import os

#: The profiler line whose events are the device's operations.
OPS_LINE = "XLA Ops"


def load_xplane(path: str, marker: str):
    """(device ops, marker start_ns) from an .xplane.pb file: ops of every
    TPU device plane's "XLA Ops" line, and the host event named `marker`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops = []
    mark = None
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            take = device and line.name == OPS_LINE
            for ev in line.events:
                if take:
                    ops.append((op_name(ev.name), float(ev.start_ns),
                                float(ev.end_ns)))
                elif mark is None and ev.name == marker:
                    mark = float(ev.start_ns)
    return ops, mark


def op_name(text: str) -> str:
    """The instruction name of an op event ("sort.6" of "%sort.6 = ...")."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def host_activity(spans, lo, hi):
    """[(start, end, label)] covering [lo, hi): the newest open span."""
    spans = sorted((s, e, n) for n, s, e in spans if e > s)
    bounds = sorted({lo, hi, *(t for s, e, _ in spans for t in (s, e)
                               if lo < t < hi)})
    segments = []
    heap: list[tuple[float, float, str]] = []  # (-start, end, name)
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i][0] <= a:
            s, e, n = spans[i]
            heapq.heappush(heap, (-s, e, n))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        # Entries under the top that ended are dropped when they surface.
        label = heap[0][2] if heap else "none"
        if segments and segments[-1][2] == label and segments[-1][1] == a:
            segments[-1] = (segments[-1][0], b, label)
        else:
            segments.append((a, b, label))
    return segments


def idle_gaps(busy, activity, lo, hi):
    """[(label, seconds)] of the idle stretches of [lo, hi), cut where
    the host activity label changes, longest first."""
    idle = []
    t = lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    gaps = []
    j = 0
    for s, e in idle:
        while j < len(activity) and activity[j][1] <= s:
            j += 1
        k = j
        while k < len(activity) and activity[k][0] < e:
            a, b, label = activity[k]
            piece = min(b, e) - max(a, s)
            if piece > 0:
                if gaps and gaps[-1][0] == label and gaps[-1][2] == max(a, s):
                    gaps[-1] = (label, gaps[-1][1], min(b, e))
                else:
                    gaps.append((label, max(a, s), min(b, e)))
            k += 1
    out = [(label, (b - a) / 1e9) for label, a, b in gaps]
    out.sort(key=lambda g: -g[1])
    return out


def reduce_trace(ops, spans, window, *, top: int = 10) -> dict:
    """busy_s, window_s, the `top` device operations by total seconds and
    the `top` longest idle gaps, from op intervals and host spans in ns."""
    lo, hi = window
    busy = union(_clip([(s, e) for _, s, e in ops], lo, hi))
    by_name: dict[str, float] = {}
    for name, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            by_name[name] = by_name.get(name, 0.0) + d / 1e9
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps(busy, host_activity(spans, lo, hi), lo, hi)[:top]
    return {
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, s] for n, s in device_ops],
        "idle_gaps": [[n, s] for n, s in gaps],
    }
