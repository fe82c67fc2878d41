"""Reduction of a profiler trace to device busy time, idle gaps and op times.

A trace is read once from the profiler's ``.xplane.pb`` into a plain
structure that the metric readers and the tests share::

    {"devices": {plane: [[name, start_ns, dur_ns], ...]},   # "XLA Ops" line
     "host":    [[name, start_ns, dur_ns], ...]}            # bench.* spans

Device planes are ``/device:TPU:<i>``; their "XLA Ops" line holds every
executed HLO op, nested (a ``while`` op spans its body's ops).  Host spans
are the benchmark's own `jax.profiler.TraceAnnotation`s, whose names start
with ``bench.``.  Both sit on the profiler's one clock.

Busy time is the union of a device's op intervals; an idle gap is a stretch
of the window that no op covers.  An op's self time is its duration less
the ops nested in it, so op times add up to busy time.
"""
from __future__ import annotations

import glob
import os
import re
from collections import Counter

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def load_xplane(logdir: str) -> dict:
    """The plain trace structure of the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        else:
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    host.sort(key=lambda ev: ev[1])
    return {"devices": devices, "host": host}


def op_name(full: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = full.split(" = ", 1)[0]
    return head.lstrip("%")


def merge(intervals) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted and non-overlapping."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def busy_ns(events, t0: float, t1: float) -> float:
    """Time in [t0, t1) during which some op ran."""
    spans = clip(merge((s, s + d) for _, s, d in events), t0, t1)
    return sum(e - s for s, e in spans)


def idle_gaps(events, t0: float, t1: float) -> list[tuple[float, float]]:
    """Stretches of [t0, t1) that no op covers, in time order."""
    gaps, at = [], t0
    for s, e in clip(merge((s, s + d) for _, s, d in events), t0, t1):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < t1:
        gaps.append((at, t1))
    return gaps


def self_times(events, t0: float, t1: float) -> Counter:
    """Per op name, the summed self time of the ops that start in [t0, t1).

    Ops on one line nest properly (a parent spans its children), so a
    stack sweep in start order charges each op its duration less its
    direct children's.
    """
    evs = sorted(((s, -d, name) for name, s, d in events if t0 <= s < t1))
    out: Counter = Counter()
    stack: list[list] = []          # [end, name, self_ns]
    for s, neg_d, name in evs:
        d = -neg_d
        while stack and s >= stack[-1][0]:
            end, nm, own = stack.pop()
            out[nm] += own
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, op_name(name), d])
    for _, nm, own in stack:
        out[nm] += own
    return out


def matching(events, pattern: str, t0: float, t1: float) -> list:
    """Ops starting in [t0, t1) whose name matches ``pattern`` (a regex
    searched in the op's name, i.e. the text before ``=``)."""
    rx = re.compile(pattern)
    return [ev for ev in events
            if t0 <= ev[1] < t1 and rx.search(op_name(ev[0]))]


def spans(host, name: str) -> list[tuple[float, float]]:
    """[start, end) of the host spans called ``name``, in time order."""
    return [(s, s + d) for nm, s, d in host if nm == name]


def window(trace: dict) -> tuple[float, float]:
    """The measured window: the ``bench.window`` host span."""
    (w,) = spans(trace["host"], HOST_PREFIX + "window")
    return w


def label_gap(host, gap: tuple[float, float]) -> str:
    """What the host was doing in a gap: the innermost benchmark span
    containing its midpoint (the shortest one), else ``host: outside
    benchmark spans``."""
    mid = 0.5 * (gap[0] + gap[1])
    inside = [(d, nm) for nm, s, d in host
              if s <= mid < s + d and nm != HOST_PREFIX + "window"]
    return min(inside)[1] if inside else "host: outside benchmark spans"
