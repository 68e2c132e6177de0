"""collective_exposed_pct: exchange and all-reduce time no other op hides.

100 x the device time in which a collective op (halo ``ppermute`` sends,
the ``psum`` of the recurrence scalars) runs and no other op does, over
the device's busy time, in the traced window; on several chips the mean
over the chips (``tracefile.summarize``).  The Fig. 2 halo/interior split
exists to drive it down.
"""


def read(rec):
    t = rec.trace
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["busy_s"]
