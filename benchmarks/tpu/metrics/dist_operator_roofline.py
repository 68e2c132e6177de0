"""dist_operator_roofline: the sharded operator's share of its chips' roofline.

The harness times ``PROBE_CALLS`` back-to-back standalone applies of the
driver's ``operator`` (the cell's ``dist_operator``, one program over all
of the cell's chips) and reads their device time from the trace.  The
least possible time is the algorithm's Eq. 4 work of all chips over all
chips' peaks: max(FLOPs / (chips x peak FLOP/s), bytes / (chips x HBM
bandwidth)), from ``work.py`` and ``peaks.json``, whatever implements the
operator.  It reads 1/chips of what ``operator_roofline`` (one chip's
peaks) gives for the same work and time.
"""
import numpy as np

import work

PROBE = "operator"


def read(rec):
    t = rec.probe_s.get(PROBE)
    if not t:
        return None
    c = rec.config
    n, chips = c["degree"], int(np.prod(c["grid"]))
    elems = [g * e for g, e in zip(c["grid"], c["elements_per_chip"])]
    e = int(np.prod(elems))
    flops = work.operator_flops(e, n)
    nbytes = work.operator_bytes(e, n, work.n_global(elems, n),
                                 word=np.dtype(c["dtype"]).itemsize)
    least = max(flops / (chips * rec.peaks["flops_per_s"]),
                nbytes / (chips * rec.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / t
