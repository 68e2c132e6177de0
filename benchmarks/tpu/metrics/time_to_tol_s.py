"""time_to_tol_s: window seconds over the solves that converged (host clock).

A solve whose status is not one the traffic counts as success is a
failure and adds its time but no solve.
"""


def read(rec):
    done = sum(s[1] in rec.ok_status for s in rec.stats)
    return rec.window_s / done if done else None
