"""Least time of the traced ticks' recovery forwards (operations over the bf16
peak or bytes over HBM bandwidth, whichever is larger) over the summed device
time of the recovery kernels named in kernels.json."""

import flops


def read(run):
    t = run.trace
    if t is None or not run.rec.traced_ticks:
        return None
    seconds = t.kernel_seconds(run.kernels)
    if seconds <= 0:
        return None
    ops, nbytes = flops.kernel_work(run.cfg, run.traffic["steps_per_tick"], run.rec.live_slots)
    least = run.rec.traced_ticks * max(
        ops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / seconds
