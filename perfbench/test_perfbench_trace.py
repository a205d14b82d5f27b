"""The trace reduction on a small trace recorded on the chip (TPU v5 lite).

``testdata/tiny_serve.xplane.pb`` is the profiler's trace of a few serve
ticks of a four-slot fleet, recorded by ``run.py --trace 1`` on one chip.
"""

from __future__ import annotations

from pathlib import Path

import pytest

TRACE = Path(__file__).resolve().parent / "testdata" / "tiny_serve.xplane.pb"


@pytest.fixture(scope="module")
def reduction():
    import devtrace

    return devtrace.reduce(TRACE)


def test_busy_within_window_and_devices_found(reduction):
    assert reduction.n_devices == 1
    assert 0 < reduction.busy_s < reduction.window_s


def test_recovery_kernel_time_found_by_name(reduction):
    kernels = reduction.kernel_seconds(["mr_step", "mr_tick"])
    assert 0 < kernels <= reduction.busy_s
    assert reduction.top_ops(1)[0][0].startswith("mr_step_fused")


def test_idle_gaps_attributed_to_host_spans(reduction):
    gaps = reduction.idle_gaps
    assert 0 < len(gaps) <= 10
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert all(name.startswith("bench.") or name == "other" for name, _ in gaps)
    idle = reduction.window_s - reduction.busy_s
    assert sum(s for _, s in gaps) <= idle + 1e-9
    assert set(reduction.host_spans) >= {"bench.gather", "bench.tick_once", "bench.readback"}


def test_op_names_are_instruction_and_shape():
    import devtrace

    name = devtrace.op_name(
        "%mr_step_fused.1 = f32[1024,17,45]{2,1,0:T(8,128)S(1)} custom-call(f32[1024,32,17,4])")
    assert name == "mr_step_fused.1 f32[1024,17,45]"
    assert devtrace.op_name("%while.3 = (s32[]{:T(128)}, f32[4]) while(%t)") == "while.3 (tuple)"
