"""Attribution of the device's idle time to the program's own host spans.

    python3 perfbench/spans.py [trace.xplane.pb]

The program writes ``mr.*`` spans (``jax.profiler.TraceAnnotation``) on the
same host line as the benchmark's ``bench.*`` spans. This module reads the
``.xplane.pb`` that ``run.py --trace 1`` wrote under ``.perfbench/trace``,
takes the window (the extent of the ``bench.*`` spans) and the device's busy
union as ``devtrace.reduce`` does, and splits every idle interval of each
device by the innermost host span that covers each piece of it. Shares are
averaged over the devices that ran anything, as the busy time is, so the
idle of any set of spans is at most ``idle_share`` of the same trace.
Without an argument, ``__main__`` prints the per-span idle table of the last
traced run.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import sys
from pathlib import Path

import devtrace

PREFIX = "mr."
CHURN = ("mr.evict", "mr.admit", "mr.cold_start", "mr.preempt", "mr.enqueue", "mr.snapshot")
NO_SPAN = "(no span)"
TRACE_DIR = Path(__file__).resolve().parent.parent / ".perfbench" / "trace"


@dataclasses.dataclass
class SpanIdle:
    window_s: float
    idle_s: float  # mean over the devices that ran an operation
    idle_by_span: dict  # innermost host span (or NO_SPAN) -> idle seconds, device mean
    durations: dict  # span name -> [seconds] of the spans that lie in the window

    def has(self, prefix: str) -> bool:
        """Whether a span whose name starts with ``prefix`` lies in the window."""
        return any(n.startswith(prefix) for n in self.durations)

    def idle_share(self, names=None) -> float:
        """% of the window the device was idle under one of ``names`` (every
        ``mr.*`` span when None) as the innermost span."""
        if names is None:
            names = [n for n in self.idle_by_span if n.startswith(PREFIX)]
        return 100.0 * sum(self.idle_by_span.get(n, 0.0) for n in names) / self.window_s


def base_name(name: str) -> str:
    """A span's name without the ``#key=value,...#`` metadata suffix."""
    return name.split("#", 1)[0]


def innermost(spans):
    """[(start, end, name)] -> the pieces [(start, end, name)] of the time
    covered by some span, each named by the innermost span there: the one
    that starts last, or of two that start together the one that ends first."""
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    starts = collections.defaultdict(list)
    for sp in spans:
        starts[sp[0]].append(sp)
    active, pieces = [], []
    for a, b in zip(edges, edges[1:]):
        active = [sp for sp in active + starts.get(a, []) if sp[1] > a]
        if active:
            s, e, name = max(active, key=lambda sp: (sp[0], -sp[1]))
            pieces.append((a, b, name))
    return pieces


def attribute(idle, spans) -> dict:
    """Idle intervals [(start, end)] split by the innermost covering span of
    ``spans`` [(start, end, name)]: {name: time}, with NO_SPAN for the idle
    time no span covers. The values add up to the idle time."""
    pieces = innermost(spans)
    out = collections.Counter()
    j = 0
    for g0, g1 in sorted(idle):
        covered = 0
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            s, e, name = pieces[k]
            o = min(e, g1) - max(s, g0)
            if o > 0:
                out[name] += o
                covered += o
            k += 1
        if g1 - g0 > covered:
            out[NO_SPAN] += g1 - g0 - covered
    return dict(out)


def idle_intervals(ops, w0, w1):
    """The gaps inside [w0, w1] between a device's operations [(start, end)]."""
    busy = devtrace._union([(max(s, w0), min(e, w1)) for s, e in ops if e > w0 and s < w1])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime_ns: int) -> SpanIdle:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, bench, lines = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == devtrace.OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.start_ns, e.start_ns + e.duration_ns, base_name(e.name))
                          for e in line.events]
                ours = [ev for ev in events if ev[2].startswith(devtrace.SPAN_PREFIX)]
                bench += ours
                if ours:
                    lines.append((len(ours), events))
    if not devices:
        raise ValueError(f"{path}: no device ran an operation in the trace")
    if not bench:
        raise ValueError(f"{path}: no {devtrace.SPAN_PREFIX}* host span in the trace")
    w0 = min(s for s, _, _ in bench)
    w1 = max(e for _, e, _ in bench)
    # the host line that holds the benchmark's spans holds the program's too
    _, events = max(lines, key=lambda x: x[0])
    spans = [(max(s, w0), min(e, w1), n) for s, e, n in events
             if (n.startswith(devtrace.SPAN_PREFIX) or n.startswith(PREFIX)) and e > w0 and s < w1]
    idle = collections.Counter()
    for ops in devices:
        idle.update(attribute(idle_intervals(ops, w0, w1), spans))
    durations = collections.defaultdict(list)
    for s, e, n in spans:
        durations[n].append((e - s) * 1e-9)
    n_dev = len(devices)
    return SpanIdle(
        window_s=(w1 - w0) * 1e-9,
        idle_s=sum(idle.values()) * 1e-9 / n_dev,
        idle_by_span={n: t * 1e-9 / n_dev for n, t in idle.items()},
        durations=dict(durations),
    )


def load(path) -> SpanIdle:
    """The attribution of one trace file; parsed once per file."""
    path = Path(path).resolve()
    return _load(str(path), path.stat().st_mtime_ns)


def last_run() -> SpanIdle:
    """The attribution of the trace the last ``run.py --trace 1`` wrote."""
    return load(devtrace.find_xplane(TRACE_DIR))


def table(si: SpanIdle) -> str:
    rows = [f"window {si.window_s:.3f} s, device idle {si.idle_s:.3f} s "
            f"({100 * si.idle_s / si.window_s:.2f} %)",
            f"{'innermost span':<20} {'idle s':>9} {'% window':>9} {'spans':>6} {'mean ms':>9}"]
    for name, t in sorted(si.idle_by_span.items(), key=lambda kv: -kv[1]):
        d = si.durations.get(name, [])
        mean = f"{1e3 * sum(d) / len(d):9.3f}" if d else f"{'':>9}"
        rows.append(f"{name:<20} {t:9.4f} {100 * t / si.window_s:9.2f} {len(d):6d} {mean}")
    rows.append(f"idle in mr.* {si.idle_share():.2f} %, in churn {si.idle_share(CHURN):.2f} %")
    return "\n".join(rows)


if __name__ == "__main__":
    print(table(load(sys.argv[1]) if len(sys.argv) > 1 else last_run()))
