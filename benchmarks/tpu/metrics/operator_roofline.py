"""operator_roofline: the assembled operator's share of its roofline.

The harness times ``PROBE_CALLS`` back-to-back standalone applies of the
driver's ``operator`` (the cell's ``poisson_assembled(prob)``) and reads
their device time from the trace.  The least possible time is
max(FLOPs / peak, bytes / bandwidth) for the algorithm's work, Eq. 4 in
assembled form (``work.py``), whatever implements the operator; the
peaks come from ``peaks.json`` by device kind.
"""
import numpy as np

import work

PROBE = "operator"


def read(rec):
    t = rec.probe_s.get(PROBE)
    if not t:
        return None
    c = rec.config
    n = c["degree"]
    elems = [g * e for g, e in zip(c["grid"], c["elements_per_chip"])]
    e = int(np.prod(elems))
    flops = work.operator_flops(e, n)
    nbytes = work.operator_bytes(e, n, work.n_global(elems, n),
                                 word=np.dtype(c["dtype"]).itemsize)
    least = max(flops / rec.peaks["flops_per_s"], nbytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
