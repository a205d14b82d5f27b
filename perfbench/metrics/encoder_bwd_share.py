"""Share of the traced window in which the device ran the recovery encoder's
backward: the operations whose ``tf_op`` (their HLO ``op_name``) holds the
program's device scope ``mr.encoder_bwd`` (the fused step's reference replay
and its transpose), read from the trace's device plane (scopes.py)."""

import scopes

SCOPE = "mr.encoder_bwd"


def read(run):
    return None if run.trace is None else scopes.last_run().share(SCOPE)
