"""Share of the traced window in which the device ran nothing while the
innermost host span was one of the program's own (``mr.*``); see spans.py."""

import spans


def read(run):
    if run.trace is None:
        return None
    si = spans.last_run()
    return si.idle_share() if si.has(spans.PREFIX) else None
