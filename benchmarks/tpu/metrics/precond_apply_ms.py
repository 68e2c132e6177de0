"""precond_apply_ms: device time of one M^-1 apply, in milliseconds.

The harness times ``PROBE_CALLS`` back-to-back standalone applies of the
driver's ``precond`` (the cell's ``make_preconditioner`` result) and reads
their device time from the trace.
"""
PROBE = "precond"


def read(rec):
    t = rec.probe_s.get(PROBE)
    return None if not t else 1e3 * t
