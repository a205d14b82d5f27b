"""Reduction of a profiler trace to device busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and nothing else. The device's operations are the
events of the ``XLA Ops`` line of each ``/device:`` plane, named by their HLO
instruction and result shape; busy time is the
union of their intervals inside the traced window, averaged over the devices
that ran anything. The window is the extent of the benchmark's own host spans
(``bench.*``, written with ``jax.profiler.TraceAnnotation``), which share the
profile's clock with the device events. Every idle gap on the device timeline
is attributed to the host span that overlaps it most.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
from pathlib import Path

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
TOP = 10  # idle gaps and device ops kept in a breakdown


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over the devices that ran an operation
    n_devices: int
    op_seconds: dict  # device op name -> seconds, summed over devices
    idle_gaps: list  # the TOP longest: [(host span name, seconds)], longest first
    host_spans: dict  # span name -> seconds

    def kernel_seconds(self, prefixes) -> float:
        """Summed device time of the operations whose name starts with a prefix."""
        return sum(s for name, s in self.op_seconds.items() if name.startswith(tuple(prefixes)))

    def top_ops(self, n: int = TOP) -> list:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]


def op_name(event_name: str) -> str:
    """``%mr_step_fused.1 = f32[1024,17,45]{...} custom-call(...)`` ->
    ``mr_step_fused.1 f32[1024,17,45]``: the HLO instruction and its result shape."""
    head, _, rest = event_name.partition(" = ")
    shape = "(tuple)" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}".strip()


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find_xplane(trace_dir) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def reduce(path) -> Reduction:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans = []  # (start, end, name) host spans of the benchmark
    devices = []  # per device: list of (start, end, name)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [
                (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                for line in plane.lines
                if line.name == OPS_LINE
                for e in line.events
            ]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if not devices:
        raise ValueError(f"{path}: no device ran an operation in the trace")
    if not spans:
        raise ValueError(f"{path}: no {SPAN_PREFIX}* host span in the trace")
    w0 = min(s for s, _, _ in spans)
    w1 = max(e for _, e, _ in spans)
    op_seconds = collections.Counter()
    busy_total = 0.0
    gaps = []
    for ops in devices:
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops if e > w0 and s < w1]
        for s, e, n in inside:
            op_seconds[n] += (e - s) * 1e-9
        busy = _union([(s, e) for s, e, _ in inside])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g0, g1))
    host = collections.Counter()
    for s, e, n in spans:
        host[n] += (e - s) * 1e-9
    attributed = []
    gaps.sort(key=lambda g: g[0] - g[1])
    for g0, g1 in gaps[:TOP]:
        best, over = "other", 0
        for s, e, n in spans:
            o = min(e, g1) - max(s, g0)
            if o > over:
                best, over = n, o
        attributed.append((best, (g1 - g0) * 1e-9))
    attributed.sort(key=lambda x: -x[1])
    return Reduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / len(devices),
        n_devices=len(devices),
        op_seconds=dict(op_seconds),
        idle_gaps=attributed,
        host_spans=dict(host),
    )
