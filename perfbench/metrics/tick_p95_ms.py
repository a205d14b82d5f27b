"""95th percentile of every tick in the window, each timed on the host clock
from the gather of its input to its Theta on the host."""

import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.rec.tick_s) * 1e3, 95))
