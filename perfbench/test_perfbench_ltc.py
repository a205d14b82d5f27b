"""The LTC fleet on the path the chip runs, as a CPU rehearsal at a tiny size.

The other rehearsals (``test_perfbench_cells.py``) take the reference
dispatch off the chip; here the tiny LTC cells run the fused kernel's own
dispatch, its body under the Pallas interpreter, through the harness.
"""

from __future__ import annotations

import pytest
from rehearsal import no_compile_cache, tiny  # noqa: F401  fixtures


@pytest.mark.parametrize("workload", ["tiny_ltc.tiny_serve", "tiny_ltc.tiny_backlog"])
def test_tiny_ltc_cell_on_the_kernel_path_runs_correct(tiny, monkeypatch, workload):
    """The LTC fleet on the path the chip runs: ``compile_plan`` ->
    ``RecoveryService`` with the fused kernel's own dispatch (its body under
    the Pallas interpreter, its custom VJP backward under ``mr.encoder_bwd``)
    instead of the reference dispatch the other rehearsals take off the chip,
    held to ``perfbench/reference`` by the cell's check: every Theta read in
    serve, and the first ticks' loss, moments, change and Theta, the refilled
    streams and the evicted ones after K training steps."""
    import itertools
    import types

    import cell
    import jax
    import run
    from repro.kernels import runtime as rt
    from repro.kernels.mr_step import kernel

    traced = []
    ltc_kernel = kernel.mr_step_ltc_pallas

    def counted(*args, **kw):
        traced.append(kw["interpret"])
        return ltc_kernel(*args, **kw)

    monkeypatch.setattr(kernel, "mr_step_ltc_pallas", counted)
    monkeypatch.setattr(
        rt, "resolve_dispatch",
        lambda force_reference=False, interpret=None, backend=None:
            rt.Dispatch.REFERENCE if force_reference else rt.Dispatch.INTERPRET)
    # The window's clock advances 50 ms at every reading, so the window holds
    # about ten ticks on a machine of any speed: the backlog check needs slots
    # refilled in the window and trained a tick, and the tiny queue runs dry
    # after a few dozen ticks.
    clock = itertools.count(0.0, 0.05)
    monkeypatch.setattr(cell, "time", types.SimpleNamespace(perf_counter=lambda: next(clock)))
    jax.clear_caches()
    try:
        result = run.execute(workload, 2**31 + 777, 1.0, False, root=tiny, require_chip=False,
                             log=lambda s: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert traced and all(traced)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
