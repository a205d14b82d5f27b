"""The control fails the limits: the plain reference in the program's place,
every matrix product in three bfloat16 passes (the nearest precision below the
configuration's float32), read against the reference at full float32 precision.

At the tiny size of a test; ``calibrate.py`` reads the same on the chip at
the cells' own size.
"""

from __future__ import annotations

import pytest
from rehearsal import no_compile_cache, tiny  # noqa: F401  fixtures


@pytest.mark.parametrize("workload", ["tiny_gru.tiny_serve", "tiny_gru.tiny_backlog",
                                      "tiny_ltc.tiny_serve", "tiny_ltc.tiny_backlog"])
def test_control_reads_over_a_limit(tiny, workload):
    import calibrate
    import check
    import run

    m = run.measure(workload, 31337, 0.6, False, root=tiny, require_chip=False,
                    log=lambda s: None)
    readings = calibrate.readings(m)
    assert check.judge(readings["program"], m.limits), readings["program"]
    assert not check.judge(readings["control"], m.limits), readings["control"]
    assert not check.judge(readings["altered"], m.limits), readings["altered"]
    if "half_batch" in readings:
        assert not check.judge(readings["half_batch"], m.limits), readings["half_batch"]
