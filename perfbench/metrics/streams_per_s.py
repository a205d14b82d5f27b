"""Streams evicted with their Theta in the window, over the window's seconds."""


def read(run):
    rec = run.rec
    return sum(1 for _, _, in_window in rec.evicted if in_window) / rec.window_s
