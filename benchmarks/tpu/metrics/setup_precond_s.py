"""setup_precond_s: host seconds of the preconditioner set-up (program span).

The last ``setup.precond`` span that ``repro.obs`` recorded in the run's
process: ``repro.core.precond.make_preconditioner`` of the cell, with
every pMG level's coarsening, transfers, diagonal and Lanczos interval.
A program that records no such span gives None.
"""


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    got = obs.spans("setup.precond")
    return (got[-1].end_ns - got[-1].start_ns) * 1e-9 if got else None
