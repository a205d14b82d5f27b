"""CPU rehearsals of the benchmark's cells at a tiny size.

Each tiny cell is added to a temporary copy of the benchmark as data files
and a metric reader, with no edit to a file the benchmark already has, and
runs end to end through the harness with the look for a chip skipped.
"""

from __future__ import annotations

import pytest
from rehearsal import no_compile_cache, tiny  # noqa: F401  fixtures

CELLS = ["tiny_gru.tiny_serve", "tiny_gru.tiny_backlog", "tiny_ltc.tiny_serve",
         "tiny_ltc.tiny_backlog"]


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_cell_runs_correct_with_no_compile_in_window(tiny, workload):
    import run

    lines = []
    m = run.measure(workload, 2**31 + 12345, 0.6, False, root=tiny, require_chip=False,
                    log=lines.append)
    assert m.rec.window_compiles == 0, m.rec.window_compiled
    result = run.conclude(m, trace=False)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "compared"
    names = set(result["metrics"])
    assert "setup_s" in names and "ticks_per_s" in names
    if "serve" in workload:
        assert {"samples_per_s", "tick_p95_ms"} <= names
    else:
        assert "streams_per_s" in names and m.rec.evicted
    assert any(line.startswith("window ") and '"gc_ms"' in line for line in lines)
    assert any(line.startswith("setup ") for line in lines)


def test_same_seed_same_inputs(tiny):
    import run

    a = run.measure("tiny_gru.tiny_serve", 77, 0.2, False, root=tiny, require_chip=False,
                    log=lambda s: None)
    b = run.measure("tiny_gru.tiny_serve", 77, 0.2, False, root=tiny, require_chip=False,
                    log=lambda s: None)
    assert a.service_seed == b.service_seed
    assert (a.fleet.ys == b.fleet.ys).all() and (a.fleet.us == b.fleet.us).all()
    assert sorted(a.rec.theta_log) == sorted(b.rec.theta_log)
