"""Attribution of the device's idle time to the program's spans (spans.py).

The pure attribution on hand-built intervals, then the readers on a small
trace recorded on the chip (TPU v5 lite): ``testdata/tiny_backlog.xplane.pb``
is one traced window tick of the rehearsal's tiny GRU backlog cell
(``rehearsal.py``) cut to one slot, one training step per tick and a step
budget of one, so that the tick evicts and admits, recorded through
``run.measure(..., trace=True)``. To keep the file under 1 MB, the copies of
the compiled HLO modules that the profiler stores in its ``/host:metadata``
plane were left out; nothing here reads that plane, and ``devtrace.reduce``
and ``spans.load`` give the same result with and without them.
"""

from __future__ import annotations

import shutil
import types
from pathlib import Path

import pytest

TRACE = Path(__file__).resolve().parent / "testdata" / "tiny_backlog.xplane.pb"
SERVE_TRACE = Path(__file__).resolve().parent / "testdata" / "tiny_serve.xplane.pb"


def test_innermost_span_wins():
    import spans

    got = spans.attribute(
        [(0, 100)],
        [(0, 100, "bench.tick_once"), (10, 90, "mr.tick"), (20, 40, "mr.tick.scan"),
         (25, 30, "mr.evict")],
    )
    assert got == {"bench.tick_once": 20, "mr.tick": 60, "mr.tick.scan": 15, "mr.evict": 5}


def test_pieces_of_one_gap_go_to_different_spans():
    import spans

    got = spans.attribute(
        [(5, 35), (50, 60)],
        [(0, 10, "mr.tick.dispatch"), (10, 20, "mr.admit"), (12, 18, "mr.cold_start"),
         (30, 55, "bench.gather")],
    )
    assert got == {"mr.tick.dispatch": 5, "mr.admit": 4, "mr.cold_start": 6,
                   spans.NO_SPAN: 15, "bench.gather": 10}
    assert sum(got.values()) == 30 + 10


def test_spans_that_start_together_nest_by_their_end():
    import spans

    pieces = spans.innermost([(0, 10, "outer"), (0, 4, "inner"), (4, 4, "empty")])
    assert pieces == [(0, 4, "inner"), (4, 10, "outer")]


def _share(idle, span_list, window=100.0):
    import spans

    si = spans.SpanIdle(window, sum(e - s for s, e in idle), spans.attribute(idle, span_list),
                        {n: [e - s] for s, e, n in span_list})
    return si, 100.0 * si.idle_s / window


def test_no_program_span_gives_zero():
    import spans

    si, _ = _share([(0, 30), (60, 70)], [(0, 50, "bench.gather"), (50, 100, "bench.tick_once")])
    assert si.idle_share() == 0.0 and si.idle_share(spans.CHURN) == 0.0
    assert not si.has(spans.PREFIX)


def test_shares_keep_churn_within_program_within_idle():
    import spans

    idle = [(0, 8), (12, 30), (41, 47), (70, 99)]
    span_list = [(0, 10, "bench.gather"), (10, 60, "bench.tick_once"), (11, 58, "mr.tick"),
                 (11, 14, "mr.tick.dispatch"), (20, 50, "mr.tick.scan"), (21, 29, "mr.evict"),
                 (29, 45, "mr.admit"), (30, 44, "mr.cold_start"), (60, 80, "bench.readback")]
    si, idle_share = _share(idle, span_list)
    churn, program = si.idle_share(spans.CHURN), si.idle_share()
    assert 0 < churn < program < idle_share
    assert program == pytest.approx(2 + 6 + 1 + 8 + 1 + 3 + 1 + 2)
    assert churn == pytest.approx(8 + 1 + 3 + 1)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded trace where the readers look for the last run's trace."""
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(TRACE, d / TRACE.name)
    return d.parents[2]


def test_trace_holds_the_program_spans():
    import spans

    si = spans.load(TRACE)
    for name in ("mr.tick", "mr.tick.dispatch", "mr.tick.readback", "mr.tick.scan", "mr.evict",
                 "mr.admit", "mr.cold_start", "mr.preempt"):
        assert len(si.durations.get(name, [])) >= 1, name
    assert len(si.durations["mr.tick"]) == len(si.durations["mr.tick.dispatch"])
    assert TRACE.stat().st_size < 1 << 20
    assert spans.load(TRACE) is si  # parsed once


def test_readers_in_range_on_the_recorded_trace(recorded, monkeypatch):
    import devtrace
    import run
    import spans

    monkeypatch.setattr(spans, "TRACE_DIR", recorded)
    reduction = devtrace.reduce(TRACE)
    traced = types.SimpleNamespace(trace=reduction)
    root = Path(__file__).resolve().parent
    value = {name: run.reader(root, name)(traced)
             for name in ("idle_share.backlog", "idle_in_program.backlog",
                          "idle_in_churn.backlog", "admit_ms.backlog", "idle_in_program.serve")}
    assert 0 < value["idle_in_churn.backlog"] <= value["idle_in_program.backlog"]
    assert value["idle_in_program.backlog"] <= value["idle_share.backlog"] + 1e-9
    assert value["idle_in_program.serve"] == value["idle_in_program.backlog"]
    assert 0 < value["admit_ms.backlog"] < 1e3 * reduction.window_s
    si = spans.last_run()
    assert sum(si.idle_by_span.values()) == pytest.approx(reduction.window_s - reduction.busy_s)
    untraced = types.SimpleNamespace(trace=None)
    for name in value:
        if name != "idle_share.backlog":
            assert run.reader(root, name)(untraced) is None


def test_readers_give_nothing_for_a_program_without_spans(tmp_path, monkeypatch):
    """A trace of a program that writes no ``mr.*`` span (the serve trace
    recorded before the spans existed): the readers return None, not 0."""
    import devtrace
    import run
    import spans

    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(SERVE_TRACE, d / SERVE_TRACE.name)
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    traced = types.SimpleNamespace(trace=devtrace.reduce(SERVE_TRACE))
    root = Path(__file__).resolve().parent
    for name in ("idle_in_program.serve", "idle_in_churn.backlog", "admit_ms.backlog"):
        assert run.reader(root, name)(traced) is None
