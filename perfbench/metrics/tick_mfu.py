"""The tick's required operations (counted from shapes) times ticks per second
of the traced window, over the chip's bf16 peak."""

import flops


def read(run):
    t = run.trace
    if t is None or not run.rec.traced_ticks:
        return None
    per_tick = flops.tick_flops(run.cfg, run.traffic["steps_per_tick"], run.rec.live_slots)
    rate = per_tick * run.rec.traced_ticks / t.window_s
    return 100.0 * rate / run.peaks["bf16_flops_per_s"]
