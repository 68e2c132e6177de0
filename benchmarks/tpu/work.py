"""Work counts of the NekBone/hipBone algorithm, from shapes alone.

A frozen copy of the paper's counts (arXiv:2202.12477, Eqs. 3-4), kept with
the benchmark so that no change to the program can change the yardstick.
E is the element count (summed over chips), N the degree, ``word`` the
bytes of one value and ``index`` the bytes of one l2g index.
"""
from __future__ import annotations


def n_local(e: int, n: int) -> int:
    """N_L: element-local nodes, E (N+1)^3."""
    return e * (n + 1) ** 3


def n_global(elems: tuple[int, int, int], n: int) -> int:
    """N_G: assembled DOFs of a box of elems = (ex, ey, ez) elements."""
    ex, ey, ez = elems
    return (ex * n + 1) * (ey * n + 1) * (ez * n + 1)


def nekbone_flops_per_iter(e: int, n: int) -> int:
    """Eq. 3, the NekBone figure of merit: 12 E (N+1)^4 + 34 E (N+1)^3."""
    return 12 * e * (n + 1) ** 4 + 34 * e * (n + 1) ** 3


def operator_flops(e: int, n: int) -> int:
    """Eq. 4 operator FLOPs, assembled form: 12 E (N+1)^4 + 18 E (N+1)^3."""
    return 12 * e * (n + 1) ** 4 + 18 * e * (n + 1) ** 3


def operator_bytes(e: int, n: int, n_g: int, *, word: int = 4, index: int = 4) -> int:
    """Eq. 4 operator bytes, assembled form.

    x_G read and y_G write (2 word N_G), plus per local node the l2g index,
    six geometric factors and the weight W ((index + 7 word) N_L).  The
    geometric factors count as per-node data, as hipBone stores them.
    """
    return 2 * word * n_g + (index + 7 * word) * n_local(e, n)
