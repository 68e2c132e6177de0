"""setup_s: process start until the first timed solve may start (host clock).

Problem build, preconditioner set-up, compile or cache load and the
warm-up solve.
"""


def read(rec):
    return rec.setup_s
