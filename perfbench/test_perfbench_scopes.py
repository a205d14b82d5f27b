"""Device time under the program's named scopes (scopes.py, encoder_bwd_share).

The union of scoped intervals on hand-built input, then the reader on a
small trace recorded on the chip (TPU v5 lite):
``testdata/tiny_ltc_backlog.xplane.pb`` is one traced window tick of the
rehearsal's tiny LTC backlog cell (``rehearsal.py``) cut to one slot, one
training step per tick and a step budget of one, recorded through
``run.measure(..., trace=True)``. The copies of the compiled HLO modules that
the profiler keeps in its ``/host:metadata`` plane were left out to keep the
file small; nothing here reads that plane. The older
``testdata/tiny_backlog.xplane.pb`` was recorded from a program without the
scope: there the reader gives None, as the span readers do for a program
without spans.
"""

from __future__ import annotations

import shutil
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
TRACE = HERE / "testdata" / "tiny_ltc_backlog.xplane.pb"
UNSCOPED_TRACE = HERE / "testdata" / "tiny_backlog.xplane.pb"
SCOPE = "mr.encoder_bwd"


def test_scoped_seconds_are_a_union_averaged_over_devices():
    import scopes

    inside = f"jit(tick)/transpose(jvp({SCOPE}))/dot"
    dev0 = [(0, 10_000, inside), (5_000, 20_000, inside), (20_000, 30_000, "jit(tick)/while"),
            (40_000, 50_000, inside)]
    dev1 = [(0, 10_000, inside), (10_000, 60_000, "jit(tick)/while")]
    so = scopes.ScopedOps(window_s=100e-6, devices=[dev0, dev1])
    assert so.seconds(SCOPE) == pytest.approx((30e-6 + 10e-6) / 2)
    assert so.share(SCOPE) == pytest.approx(20.0)
    assert so.seconds("mr.not_there") is None and so.share("mr.not_there") is None


def test_tf_ops_name_the_recorded_device_ops():
    import scopes

    planes = scopes.tf_ops(TRACE.read_bytes())
    ops = planes["/device:TPU:0"]
    scoped = [op for op in ops.values() if SCOPE in op]
    # the custom VJP backward runs in the transpose of the training step's jvp
    assert scoped and all(f"transpose(jvp({SCOPE}))" in op for op in scoped)
    kernels = [event for event in ops if event.startswith("%mr_step_fused_ltc")]
    assert kernels and not any(SCOPE in ops[event] for event in kernels)
    assert sum(op.startswith("jit(tick)/") for op in ops.values()) > 100


def test_scoped_ops_hold_every_device_op_of_the_window():
    import devtrace
    import scopes

    reduction = devtrace.reduce(TRACE)
    so = scopes.load(TRACE)
    assert so.window_s == pytest.approx(reduction.window_s)
    assert len(so.devices) == reduction.n_devices == 1
    seconds = sum(e - s for s, e, _ in so.devices[0]) * 1e-9
    assert seconds == pytest.approx(sum(reduction.op_seconds.values()))
    assert 0 < so.seconds(SCOPE) < reduction.busy_s


@pytest.fixture()
def recorded(tmp_path, monkeypatch):
    """``path`` copied where the readers look for the last run's trace."""
    import spans

    def place(path):
        d = tmp_path / path.stem / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copy(path, d / path.name)
        monkeypatch.setattr(spans, "TRACE_DIR", d.parents[2])

    return place


def test_reader_on_the_recorded_scoped_trace(recorded):
    import devtrace
    import run
    import scopes

    assert TRACE.stat().st_size < 1 << 20
    reduction = devtrace.reduce(TRACE)
    recorded(TRACE)
    value = run.reader(HERE, "encoder_bwd_share.backlog")(types.SimpleNamespace(trace=reduction))
    assert 0 < value < 100
    assert value == pytest.approx(100 * scopes.load(TRACE).seconds(SCOPE) / reduction.window_s)
    assert run.reader(HERE, "encoder_bwd_share.backlog")(types.SimpleNamespace(trace=None)) is None


def test_reader_gives_nothing_for_a_program_without_the_scope(recorded):
    import devtrace
    import run
    import scopes

    assert scopes.load(UNSCOPED_TRACE).seconds(SCOPE) is None
    recorded(UNSCOPED_TRACE)
    traced = types.SimpleNamespace(trace=devtrace.reduce(UNSCOPED_TRACE))
    assert run.reader(HERE, "encoder_bwd_share.backlog")(traced) is None
