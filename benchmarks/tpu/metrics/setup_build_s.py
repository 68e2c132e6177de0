"""setup_build_s: host seconds of the problem build in set-up (program span).

The last ``setup.build_problem`` span that ``repro.obs`` recorded in the
run's process: mesh, geometric factors and the upload of the cell's
problem (``repro.core.build_problem``).  A program that records no such
span gives None.
"""


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    got = obs.spans("setup.build_problem")
    return (got[-1].end_ns - got[-1].start_ns) * 1e-9 if got else None
