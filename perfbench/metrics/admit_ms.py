"""Mean duration of the program's ``mr.admit`` spans in the traced window
(queue pop, warm lookup, cold start, the admit program)."""

import spans


def read(run):
    if run.trace is None:
        return None
    d = spans.last_run().durations.get("mr.admit")
    return 1e3 * sum(d) / len(d) if d else None
