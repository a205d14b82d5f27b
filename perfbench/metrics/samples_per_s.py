"""Samples ingested per second: live slots x chunk x ticks completed in the
window, over the window's seconds (host clock)."""


def read(run):
    rec = run.rec
    return rec.live_slots * rec.chunk * len(rec.tick_s) / rec.window_s
