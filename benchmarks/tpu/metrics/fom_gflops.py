"""fom_gflops: the NekBone figure of merit over the window (host clock).

Eq. 3 FLOPs per CG iteration, 12 E (N+1)^4 + 34 E (N+1)^3 with E summed
over all chips, times every CG iteration the window's solves completed,
over the window's seconds.
"""
import numpy as np

import work


def read(rec):
    c = rec.config
    e = int(np.prod(c["elements_per_chip"])) * int(np.prod(c["grid"]))
    iterations = sum(s[0] for s in rec.stats)
    return work.nekbone_flops_per_iter(e, c["degree"]) * iterations / rec.window_s / 1e9
