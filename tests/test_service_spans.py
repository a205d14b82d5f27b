"""The service's profiler spans (``mr.*``, core/stream.py) in a recorded trace.

A small fleet runs under ``jax.profiler`` into a temporary directory; the
trace is read back with ``ProfileData``. Checks: one ``mr.tick`` per tick,
the nesting of the host plane's spans, the metadata of eviction and
admission spans against what the service returned, and the device control
plane's ``mr.snapshot`` / ``mr.enqueue`` / ``mr.pump``.
"""

from __future__ import annotations

import glob
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api
from repro.api import RecoverySpec, TickSpec
from repro.core.stream import StreamConfig
from repro.data.dynamics import generate_trajectory

# evictions every 2 ticks (max_steps = 2 ticks of K=8), so refills come fast
SCFG = StreamConfig(
    buf_len=32, window=8, stride=8, chunk=8, steps_per_tick=8, min_steps=16, max_steps=16,
    delta_tol=0.0,
)
BASE = dict(state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01)
N_STREAMS, SLOTS, TICKS = 6, 2, 8


@pytest.fixture(scope="module")
def lorenz():
    _, ys, _ = generate_trajectory("lorenz", n_samples=200)
    return np.asarray(ys, np.float32)


def _service(control: str, snapshot_period: int = 1):
    spec = RecoverySpec(
        mode="stream", n_slots=SLOTS, stream=SCFG, encoder="gru", seed=0,
        tick=TickSpec(steps_per_tick=SCFG.steps_per_tick, control=control, queue_capacity=8,
                      snapshot_period=snapshot_period, warm_capacity=8),
        **BASE,
    )
    plan = api.compile_plan(spec)
    return plan, plan.make_service()


def _spans(trace_dir) -> list[tuple[float, float, str, dict]]:
    """(start, end, name, metadata) of every ``mr.*`` event, by start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("mr."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _inside(child, parents) -> bool:
    return any(p[0] <= child[0] and child[1] <= p[1] for p in parents)


def _feed(svc, data, cursors):
    chunk = np.zeros((SLOTS, SCFG.chunk, 3), np.float32)
    for s, sid in enumerate(svc.slot_streams()):
        if sid >= 0:
            idx = (cursors[sid] + np.arange(SCFG.chunk)) % data.shape[1]
            chunk[s] = data[sid, idx]
            cursors[sid] += SCFG.chunk
    return chunk


def _streams(lorenz):
    return np.stack([np.roll(lorenz, -9 * i, axis=0) for i in range(N_STREAMS)])


def test_host_plane_spans_nest_and_carry_ids(lorenz, tmp_path):
    data = _streams(lorenz)
    plan, svc = _service("host")
    for sid in range(N_STREAMS):
        svc.submit(sid, data[sid, : SCFG.buf_len])
    svc.fill_slots()
    cursors = dict.fromkeys(range(N_STREAMS), SCFG.buf_len)
    infos, refills = [], []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(TICKS):
            before = svc.slot_streams()
            infos.append(svc.tick_once(_feed(svc, data, cursors)))
            after = svc.slot_streams()
            refills += [(s, a) for s, (b, a) in enumerate(zip(before, after)) if a >= 0 and a != b]
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    ticks = _named(spans, "mr.tick")
    assert [s[3]["tick"] for s in ticks] == [i["tick"] for i in infos]
    scans = _named(spans, "mr.tick.scan")
    assert len(scans) == TICKS and all(_inside(s, ticks) for s in scans)
    assert len(_named(spans, "mr.tick.dispatch")) == TICKS
    # the banked tick packs the status into one read; the composite tick
    # reads its leaves, then the loss after the scan
    per_tick = 1 if plan.lowering.tick_kernel == "banked" else 2
    readbacks = _named(spans, "mr.tick.readback")
    assert len(readbacks) == per_tick * TICKS and all(_inside(s, ticks) for s in readbacks)

    evicts, admits = _named(spans, "mr.evict"), _named(spans, "mr.admit")
    evicted = [(r.stream_id) for i in infos for r in i["evicted"]]
    assert evicted and admits
    assert [s[3]["stream_id"] for s in evicts] == evicted
    assert [(s[3]["slot"], s[3]["stream_id"]) for s in admits] == refills
    assert all(_inside(s, scans) for s in evicts + admits)
    # every admission here is a new stream: each cold-starts inside its span
    assert len(_named(spans, "mr.cold_start")) == len(admits)
    assert all(_inside(s, admits) for s in _named(spans, "mr.cold_start"))
    # the preemption pass runs (inside the scan) on every tick an arrival waits
    preempts = _named(spans, "mr.preempt")
    assert preempts and all(_inside(s, scans) for s in preempts)
    assert not _named(spans, "mr.snapshot") and not _named(spans, "mr.enqueue")


def test_device_plane_spans(lorenz, tmp_path):
    data = _streams(lorenz)
    _, svc = _service("device", snapshot_period=2)
    cursors = dict.fromkeys(range(N_STREAMS), SCFG.buf_len)
    infos = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for sid in range(N_STREAMS):
            svc.submit(sid, data[sid, : SCFG.buf_len], priority=0)
        svc.fill_slots()
        for _ in range(TICKS):
            infos.append(svc.tick_once(_feed(svc, data, cursors)))
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    ticks = _named(spans, "mr.tick")
    assert [s[3]["tick"] for s in ticks] == [i["tick"] for i in infos]
    assert len(_named(spans, "mr.tick.dispatch")) == TICKS
    enqueues = _named(spans, "mr.enqueue")
    assert [s[3]["stream_id"] for s in enqueues] == list(range(N_STREAMS))
    cold = _named(spans, "mr.cold_start")
    assert len(cold) == N_STREAMS and all(_inside(s, enqueues) for s in cold)
    (pump,) = _named(spans, "mr.pump")
    snapshots = _named(spans, "mr.snapshot")
    # one inside the pump, then one every snapshot_period ticks
    assert len(snapshots) == 1 + TICKS // 2
    assert _inside(snapshots[0], [pump])
    assert all(_inside(s, ticks) for s in snapshots[1:])
    assert sum(len(i["evicted"]) for i in infos) > 0
    # the host plane's per-tick spans do not exist on this plane
    for name in ("mr.tick.scan", "mr.tick.readback", "mr.evict", "mr.admit", "mr.preempt"):
        assert not _named(spans, name), name


def test_checkpoint_span_only_on_saving_ticks(lorenz, tmp_path):
    from repro.runtime.resilience import ServiceCheckpointer

    data = _streams(lorenz)
    _, svc = _service("host")
    svc.checkpointer = ServiceCheckpointer(str(tmp_path / "ckpt"), period=3)
    for sid in range(N_STREAMS):
        svc.submit(sid, data[sid, : SCFG.buf_len])
    svc.fill_slots()
    cursors = dict.fromkeys(range(N_STREAMS), SCFG.buf_len)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for _ in range(TICKS):
            svc.tick_once(_feed(svc, data, cursors))
    finally:
        jax.profiler.stop_trace()
        svc.checkpointer.wait()
    spans = _spans(tmp_path / "trace")
    saves = _named(spans, "mr.checkpoint")
    assert len(saves) == TICKS // 3
    saving = [s for s in _named(spans, "mr.tick") if s[3]["tick"] % 3 == 0]
    assert all(_inside(s, saving) for s in saves)
