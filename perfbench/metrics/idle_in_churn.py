"""Share of the traced window in which the device ran nothing while the
innermost host span was the program's eviction, admission or queue work
(``spans.CHURN``: ``mr.evict``, ``mr.admit``, ``mr.cold_start``,
``mr.preempt``, ``mr.enqueue``, ``mr.snapshot``)."""

import spans


def read(run):
    if run.trace is None:
        return None
    si = spans.last_run()
    return si.idle_share(spans.CHURN) if si.has(spans.PREFIX) else None
