"""Device time under a named scope of the program, from a profiler trace.

    python3 perfbench/scopes.py [trace.xplane.pb] [scope ...]

JAX writes the name stack of every operation (``jax.named_scope``) into the
``op_name`` metadata of the compiled HLO instructions, and the profiler
copies it to each ``XLA Ops`` event's metadata on the device plane, as the
``tf_op`` stat. ``seconds(scope)`` is the union of the device intervals,
inside the traced window (the extent of the ``bench.*`` spans, as
``devtrace.reduce`` takes it), of the operations whose ``tf_op`` holds
``scope``, averaged over the devices that ran anything, as the busy time is.
It is None where no operation of the window runs under the scope (a program
without it).

A fused instruction carries the ``op_name`` of its root, so an XLA fusion
across a scope's edge counts wholly to one side. Events without a ``tf_op``
count to no scope: instructions the compiler adds without metadata, and the
``while`` loops themselves, whose bodies' operations carry it (so the
device's gaps between those operations inside a scoped loop do not count).
``ProfileData`` gives an event's own stats but not its metadata's, so the
``tf_op`` stats are read from the file's bytes with the small protobuf wire
reader below (XSpace -> device XPlane -> XEventMetadata -> XStat).
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from pathlib import Path

import devtrace
import spans

TF_OP_STAT = "tf_op"
DEFAULT_SCOPES = ("mr.encoder_bwd",)


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of a message: an int for a varint, bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _map_values(plane, number: int):
    """The values of the map field ``number`` of a message (entries: key 1, value 2)."""
    for f, entry in _fields(plane):
        if f == number:
            yield next((v for g, v in _fields(entry) if g == 2), b"")


def tf_ops(data: bytes) -> dict:
    """{device plane name: {event name: tf_op}} of a serialized XSpace."""
    out = {}
    for number, plane in _fields(memoryview(data)):  # XSpace.planes = 1
        if number != 1:
            continue
        name = next((bytes(v) for f, v in _fields(plane) if f == 2), b"").decode()  # XPlane.name
        if not name.startswith("/device:"):
            continue
        stat_ids = {  # XPlane.stat_metadata = 5: XStatMetadata id = 1, name = 2
            dict(_fields(meta)).get(1, 0) for meta in _map_values(plane, 5)
            if bytes(dict(_fields(meta)).get(2, b"")) == TF_OP_STAT.encode()}
        ops = {}
        for meta in _map_values(plane, 4):  # XPlane.event_metadata = 4
            event = stat = None
            for f, v in _fields(meta):  # XEventMetadata: name = 2, stats = 5
                if f == 2:
                    event = bytes(v).decode()
                elif f == 5:
                    fields = dict(_fields(v))  # XStat: metadata_id = 1, str_value = 5
                    if fields.get(1) in stat_ids and 5 in fields:
                        stat = bytes(fields[5]).decode()
            if event is not None and stat is not None:
                ops[event] = stat
        out[name] = ops
    return out


@dataclasses.dataclass
class ScopedOps:
    window_s: float
    devices: list  # per device that ran an operation: [(start_ns, end_ns, tf_op)] in the window

    def seconds(self, scope: str) -> float | None:
        """Device seconds under ``scope`` (device mean), None where no
        operation of the window runs under it."""
        per_device = [[(s, e) for s, e, n in ops if scope in n] for ops in self.devices]
        if not any(per_device):
            return None
        total = sum(e - s for ivs in per_device for s, e in devtrace._union(ivs))
        return total * 1e-9 / len(self.devices)

    def share(self, scope: str) -> float | None:
        """% of the window in which the device ran operations under ``scope``."""
        s = self.seconds(scope)
        return None if s is None else 100.0 * s / self.window_s


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime_ns: int) -> ScopedOps:
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    names = tf_ops(raw)
    bench, devices = [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:"):
            ops = [(e.start_ns, e.start_ns + e.duration_ns, names[plane.name].get(e.name, ""))
                   for line in plane.lines if line.name == devtrace.OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            bench += [(e.start_ns, e.start_ns + e.duration_ns) for line in plane.lines
                      for e in line.events if e.name.startswith(devtrace.SPAN_PREFIX)]
    if not devices:
        raise ValueError(f"{path}: no device ran an operation in the trace")
    if not bench:
        raise ValueError(f"{path}: no {devtrace.SPAN_PREFIX}* host span in the trace")
    w0, w1 = min(s for s, _ in bench), max(e for _, e in bench)
    inside = [[(max(s, w0), min(e, w1), n) for s, e, n in ops if e > w0 and s < w1]
              for ops in devices]
    return ScopedOps(window_s=(w1 - w0) * 1e-9, devices=inside)


def load(path) -> ScopedOps:
    """The scoped device operations of one trace file, parsed once per file."""
    path = Path(path).resolve()
    return _load(str(path), path.stat().st_mtime_ns)


def last_run() -> ScopedOps:
    """The scoped operations of the trace the last ``run.py --trace 1`` wrote."""
    return load(devtrace.find_xplane(spans.TRACE_DIR))


if __name__ == "__main__":
    so = load(Path(sys.argv[1]) if len(sys.argv) > 1 else devtrace.find_xplane(spans.TRACE_DIR))
    for scope in sys.argv[2:] or DEFAULT_SCOPES:
        s = so.seconds(scope)
        if s is None:
            print(f"{scope}: no operation in the window")
        else:
            print(f"{scope}: {s:.4f} s of {so.window_s:.4f} s ({so.share(scope):.2f} %)")
