"""device_idle_pct: share of the traced window with no op on the device.

1 - (union of the device's op intervals) / window, from the profiler's
device trace; on several chips the mean over the chips.
"""


def read(rec):
    t = rec.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
