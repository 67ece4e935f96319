"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, device time per operation and per program,
and the device's idle gaps by what the host was doing meanwhile.

Planes whose name starts with ``/device:TPU`` are devices; every other
plane is the host.  On a device plane the ``XLA Ops`` line holds one
event per operation and the ``XLA Modules`` line one per program run
(``jit_<function>``); busy time is the union of the operation intervals.
The host is traced at level 0 (the host CPU device's own XLA
operations would swamp the trace), so what the host was doing comes
from the benchmark's own spans, taken on the host clock from the start
of the trace (:class:`SpanLog`).  The window runs from the first span's
start to the last span's end; an idle gap of the device inside it is
named by the shortest span that covers its midpoint, and gaps are
summed per name.
"""

from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

#: program names of the Reed-Solomon coding kernel (``kernels/rs_bitmatmul.py``).
CODING_KERNEL = ("jit_gf_bitmatmul",)
DEVICE_PREFIX = "/device:TPU"
TOP = 10


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_name(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    """``jit_f(12)`` -> ``jit_f``: one key per program, not per run."""
    return name.split("(", 1)[0]


class SpanLog:
    """The benchmark's host spans, in ns from :meth:`start` (called right
    after the profiler starts, whose events count from the same moment)."""

    def __init__(self):
        self.origin = time.perf_counter_ns()
        self.spans: list[tuple[str, int, int]] = []

    def start(self) -> None:
        self.origin = time.perf_counter_ns()
        self.spans = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0 - self.origin, time.perf_counter_ns() - t0))


def reduce_planes(planes, spans=()) -> dict:
    """Reduce planes of ``(name, [(line_name, [(event, start_ns, dur_ns)])])``
    and host spans ``(name, start_ns, dur_ns)``."""
    ops = collections.Counter()
    modules = collections.Counter()
    busy_by_device = []
    for plane_name, lines in planes:
        if plane_name.startswith(DEVICE_PREFIX):
            by_line = dict(lines)
            op_events = by_line.get("XLA Ops") or by_line.get("XLA Modules") or []
            for name, start, dur in op_events:
                ops[_op_name(name)] += dur
            for name, start, dur in by_line.get("XLA Modules", []):
                modules[_module_name(name)] += dur
            busy_by_device.append(_merge((s, s + d) for _, s, d in op_events))
    busy_ns = sum(e - s for dev in busy_by_device for s, e in dev)
    n_dev = max(1, len(busy_by_device))
    gaps = collections.Counter()
    host_events = list(spans)
    if host_events:
        lo = min(s for _, s, _ in host_events)
        hi = max(s + d for _, s, d in host_events)
        busy = _merge(iv for dev in busy_by_device for iv in dev)
        idle, t = [], lo
        for s, e in busy:
            if s > t:
                idle.append((t, min(s, hi)))
            t = max(t, e)
            if t >= hi:
                break
        if t < hi:
            idle.append((t, hi))
        idle = [(a, b) for a, b in idle if b > a]
        starts = np.array([s for _, s, _ in host_events], dtype=np.int64)
        durs = np.array([d for _, _, d in host_events], dtype=np.int64)
        for a, b in idle:
            mid = (a + b) // 2
            cover = np.nonzero((starts <= mid) & (starts + durs >= mid))[0]
            name = host_events[cover[np.argmin(durs[cover])]][0] if cover.size else "(no span)"
            gaps[name] += b - a
    return {
        "busy_s": busy_ns / 1e9 / n_dev,
        "modules_s": {k: v / 1e9 for k, v in modules.items()},
        "device_ops": [[k, v / 1e9] for k, v in ops.most_common(TOP)],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps.most_common(TOP)],
    }


def load_planes(path: str):
    """Planes of an ``.xplane.pb`` as plain tuples (jax's reader)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        (plane.name, [(line.name, [(e.name, e.start_ns, e.duration_ns) for e in line.events])
                      for line in plane.lines])
        for plane in data.planes
    ]


def reduce_file(path: str, spans=()) -> dict:
    return reduce_planes(load_planes(path), spans)


def kernel_seconds(summary: dict, programs) -> float:
    """Summed device time of the named programs in the traced window."""
    return sum(v for k, v in summary["modules_s"].items() if k in programs)
