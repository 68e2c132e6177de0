"""collective_pct: share of the device's busy time that collective ops run.

100 x the union of the collective ops' intervals (halo ``ppermute``
sends, the ``psum`` of the recurrence scalars) over the device's busy
time, in the traced window; on several chips the mean over the chips
(``tracefile.summarize``).
"""


def read(rec):
    t = rec.trace
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
