"""setup_dist_build_s: host seconds of the sharded problem build (program span).

The last ``setup.build_dist_problem`` span that ``repro.obs`` recorded in
the run's process: per-rank l2g, geometric factors, inverse degree and
masks on the host, and their upload (``repro.core.distributed
.build_dist_problem``).  A program that records no such span gives None.
"""


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    got = obs.spans("setup.build_dist_problem")
    return (got[-1].end_ns - got[-1].start_ns) * 1e-9 if got else None
