"""Gather/scatter operators Z, Z^T, ZZ^T and the inverse-degree weight W.

Terminology follows the paper:
  Z      ('scatter'):  x_L = Z x_G      — copy each global DOF to every
                                          element-local node that shares it.
  Z^T    ('gather'):   b_G = Z^T y_L    — sum element-local contributions
                                          into the assembled DOF vector.
  ZZ^T   ('gather-scatter'): the NekBone combined operation on scattered
                             vectors (sum shared values, write the sum back
                             to every copy).
  W:     diagonal inverse-degree weights with Z^T W Z = I; used (a) fused
         into the hipBone operator kernel as the screen term λW, and (b) as
         the weighting for inner products on scattered vectors in the
         NekBone baseline.

Two forms of Z and Z^T, the same operators:
  indexed  (``scatter`` / ``gather``): an XLA ``take`` and a ``segment_sum``
           scatter-add through the l2g map; any connectivity.
  lattice  (``lattice_scatter`` / ``lattice_gather``): for the box mesh's
           numbering (``mesh.lattice_l2g``, tested by ``is_lattice``), one
           axis at a time as dense slices, pads and adds (the minor x axis
           as a product with a 0/1 matrix), with no index.
On TPU an indexed gather or scatter costs several ns per index, hundreds of
times the HBM time of its bytes; the lattice form runs at HBM speed.

Solver code takes both through one seam, an :class:`Assembly`:
:func:`assembly` is the one place that chooses between the forms, for a
whole box (one device) or for element blocks of a box (a rank's halo or
interior in its padded box); :func:`indexed_assembly` is the indexed form.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .mesh import lattice_l2g

_HI = lax.Precision.HIGHEST

__all__ = [
    "Assembly",
    "assembly",
    "indexed_assembly",
    "scatter",
    "gather",
    "gather_scatter",
    "is_lattice",
    "lattice_scatter",
    "lattice_gather",
    "scatter_masked",
    "gather_masked",
    "inverse_degree",
    "local_inverse_degree",
]


def scatter(x_g: jax.Array, l2g: jax.Array) -> jax.Array:
    """x_L = Z x_G. Shapes: x_G (N_G,), l2g (E, p) -> (E, p)."""
    return jnp.take(x_g, l2g, axis=0)


def gather(y_l: jax.Array, l2g: jax.Array, n_global: int) -> jax.Array:
    """b_G = Z^T y_L. Shapes: y_L (E, p), l2g (E, p) -> (N_G,)."""
    return jax.ops.segment_sum(
        y_l.reshape(-1), l2g.reshape(-1), num_segments=n_global
    )


def gather_scatter(y_l: jax.Array, l2g: jax.Array, n_global: int) -> jax.Array:
    """ZZ^T y_L — NekBone's combined gather-scatter on scattered vectors."""
    return scatter(gather(y_l, l2g, n_global), l2g)


Block = tuple[tuple[int, int, int], tuple[int, int, int]]


def is_lattice(
    l2g,
    shape: tuple[int, int, int],
    n_degree: int,
    blocks: Sequence[Block] | None = None,
) -> bool:
    """Whether ``l2g`` is exactly the box lattice numbering of (shape, N).

    ``blocks``, when given, are ``(origin, shape)`` element sub-boxes of the
    box (in elements along x, y, z): ``l2g`` must then number the blocks'
    elements one block after another, each block in its own lattice order,
    onto the box's points.  Host-side numpy, once at set-up: the lattice
    Z/Z^T pair may stand in for the indexed one only where this holds.
    """
    l2g = np.asarray(l2g)
    expect = lattice_l2g(shape, n_degree)
    if blocks is not None:
        ex, ey, ez = shape
        grid = expect.reshape(ez, ey, ex, -1)
        expect = np.concatenate([expect[:0]] + [
            grid[k:k + bz, j:j + by, i:i + bx].reshape(-1, grid.shape[-1])
            for (i, j, k), (bx, by, bz) in blocks
        ])
    return l2g.shape == expect.shape and bool(np.array_equal(l2g, expect))


def _expand(v: jax.Array, axis: int, ne: int, n: int) -> jax.Array:
    """One axis of Z: (..., ne*N+1, ...) -> (..., ne, N+1, ...) windows.

    Window e holds points e*N .. e*N+N: the first N of each are a reshape
    of the leading ne*N points, the last is every N-th point from N.
    """
    lead, rest = v.shape[:axis], v.shape[axis + 1:]
    head = lax.slice_in_dim(v, 0, ne * n, axis=axis)
    tail = lax.slice_in_dim(v, n, ne * n + 1, stride=n, axis=axis)
    return jnp.concatenate(
        [head.reshape(lead + (ne, n) + rest), tail.reshape(lead + (ne, 1) + rest)],
        axis=axis + 1,
    )


def _fold(w: jax.Array, axis: int, ne: int, n: int) -> jax.Array:
    """One axis of Z^T, the adjoint of :func:`_expand`.

    The last node of window e is the first of window e+1: it is added into
    that column, the windows' first N nodes are flattened, and the last
    window's last node is appended.
    """
    lead, rest = w.shape[:axis], w.shape[axis + 2:]
    first = lax.slice_in_dim(w, 0, 1, axis=axis + 1)
    last = lax.slice_in_dim(w, n, n + 1, axis=axis + 1)
    carry = lax.pad(
        lax.slice_in_dim(last, 0, ne - 1, axis=axis),
        jnp.zeros((), w.dtype),
        [(1, 0, 0) if d == axis else (0, 0, 0) for d in range(w.ndim)],
    )
    body = jnp.concatenate(
        [first + carry, lax.slice_in_dim(w, 1, n, axis=axis + 1)], axis=axis + 1
    )
    return jnp.concatenate(
        [
            body.reshape(lead + (ne * n,) + rest),
            lax.slice_in_dim(last, ne - 1, ne, axis=axis).reshape(lead + (1,) + rest),
        ],
        axis=axis,
    )


def _windows(ne: int, n: int, dtype) -> np.ndarray:
    """:func:`_expand` of the minor axis as a 0/1 matrix (ne*N+1, ne*(N+1)).

    Column e*(N+1) + a picks point e*N + a.  As a product its output rows
    are ne*(N+1) wide and lane-dense, where a last axis of N+1 is not; each
    output is one input times 1, so at ``Precision.HIGHEST`` it is exact.
    """
    col = np.arange(ne * (n + 1))
    e, a = np.divmod(col, n + 1)
    m = np.zeros((ne * n + 1, ne * (n + 1)), dtype)
    m[e * n + a, col] = 1
    return m


# jitted so that an eager apply (set-up's Lanczos steps) compiles one program
# per shape, not one per slice, pad and concatenation
@functools.partial(jax.jit, static_argnums=(1, 2))
def lattice_scatter(
    x_g: jax.Array, shape: tuple[int, int, int], n_degree: int
) -> jax.Array:
    """x_L = Z x_G on the box lattice. Shapes: x_G (N_G,) -> (E, p).

    Equals ``scatter(x_g, lattice_l2g(shape, n_degree))`` exactly for
    finite ``x_g`` (through the product a NaN or Inf spreads along x).  The
    major axes (z, then y) expand by slices along leading dimensions, the
    minor x axis last by a 0/1 matrix (:func:`_windows`); one transpose
    then orders (k, j, i, c, b, a).
    """
    ex, ey, ez = shape
    n = int(n_degree)
    n1 = n + 1
    v = x_g.reshape(ez * n + 1, ey * n + 1, ex * n + 1)
    v = _expand(v, 0, ez, n)  # (k, c, gy, gx)
    v = _expand(v, 2, ey, n)  # (k, c, j, b, gx)
    v = jnp.matmul(v, _windows(ex, n, v.dtype), precision=_HI)  # (k, c, j, b, i a)
    v = v.reshape(ez, n1, ey, n1, ex, n1).transpose(0, 2, 4, 1, 3, 5)
    return v.reshape(ex * ey * ez, n1**3)


@functools.partial(jax.jit, static_argnums=(1, 2))
def lattice_gather(
    y_l: jax.Array, shape: tuple[int, int, int], n_degree: int
) -> jax.Array:
    """b_G = Z^T y_L on the box lattice. Shapes: y_L (E, p) -> (N_G,).

    Equals ``gather(y_l, lattice_l2g(shape, n_degree), N_G)`` up to the
    order of each shared point's sum: :func:`lattice_scatter` in reverse,
    x by the transposed 0/1 matrix, then y and z by :func:`_fold`.
    """
    ex, ey, ez = shape
    n = int(n_degree)
    n1 = n + 1
    w = y_l.reshape(ez, ey, ex, n1, n1, n1).transpose(0, 3, 1, 4, 2, 5)
    w = w.reshape(ez, n1, ey, n1, ex * n1)
    w = jnp.matmul(w, _windows(ex, n, w.dtype).T, precision=_HI)  # (k, c, j, b, gx)
    w = _fold(w, 2, ey, n)  # (k, c, gy, gx)
    w = _fold(w, 0, ez, n)  # (gz, gy, gx)
    return w.reshape(-1)


class Assembly(NamedTuple):
    """Z and Z^T of one element-to-point map, and the form they take.

    ``z``: (N_G,) -> (E, p); ``zt``: (E, p) -> (N_G,); ``kind``: "lattice"
    or "indexed".
    """

    z: Callable[[jax.Array], jax.Array]
    zt: Callable[[jax.Array], jax.Array]
    kind: str


def indexed_assembly(l2g, n_points: int) -> Assembly:
    """Z and Z^T as the take and segment sum through ``l2g`` (any map)."""
    l2g = jnp.asarray(l2g)
    return Assembly(
        lambda x: scatter(x, l2g), lambda y: gather(y, l2g, n_points), "indexed"
    )


def assembly(
    l2g,
    n_degree: int,
    box_shape: tuple[int, int, int],
    blocks: Sequence[Block] | None = None,
) -> Assembly:
    """Z and Z^T of ``l2g`` onto the points of an element box: the lattice
    form where ``l2g`` is the lattice numbering of ``blocks``, else indexed.

    ``box_shape`` is the box in elements (x, y, z); its points are the
    lattice ``(ez*N+1, ey*N+1, ex*N+1)``, x fastest.  ``blocks`` (default:
    the whole box) are ``(origin, shape)`` element sub-boxes, as in
    :func:`is_lattice`.  One whole-box block is :func:`lattice_scatter` /
    :func:`lattice_gather` as they are; several blocks scatter from each
    block's slice of the box, their element arrays stacked in block order,
    and gather each block's rows padded back into the box and summed (blocks
    share face points, and the sum adds them as the segment sum does).
    """
    shape = tuple(int(e) for e in box_shape)
    n = int(n_degree)
    whole = [((0, 0, 0), shape)]
    blocks = whole if blocks is None else list(blocks)
    box3 = tuple(e * n + 1 for e in shape[::-1])  # (z, y, x) points
    if not is_lattice(l2g, shape, n, blocks):
        return indexed_assembly(l2g, int(np.prod(box3)))
    if blocks == whole:
        return Assembly(
            lambda x: lattice_scatter(x, shape, n),
            lambda y: lattice_gather(y, shape, n),
            "lattice",
        )
    # per block: its (z, y, x) point window in the box, and element count
    wins = [
        tuple(slice(o * n, (o + e) * n + 1) for o, e in zip(org[::-1], shp[::-1]))
        for org, shp in blocks
    ]
    counts = [int(np.prod(shp)) for _, shp in blocks]

    def z(x: jax.Array) -> jax.Array:
        x3 = x.reshape(box3)
        return jnp.concatenate([
            lattice_scatter(x3[win].reshape(-1), shp, n)
            for win, (_, shp) in zip(wins, blocks)
        ])

    def zt(y: jax.Array) -> jax.Array:
        out, start = None, 0
        for win, (_, shp), e in zip(wins, blocks, counts):
            part = lattice_gather(y[start:start + e], shp, n)
            part = jnp.pad(
                part.reshape([w.stop - w.start for w in win]),
                [(w.start, m - w.stop) for w, m in zip(win, box3)],
            )
            out = part if out is None else out + part
            start += e
        return out.reshape(-1)

    return Assembly(z, zt, "lattice")


def scatter_masked(x_g: jax.Array, l2g_ext: jax.Array) -> jax.Array:
    """Z_s x_G for maps with a dummy slot: out-of-domain entries read 0.

    The extended (overlapping-Schwarz) local-to-global maps use the index
    ``n_global`` for nodes outside the physical domain; scattering from a
    zero-padded copy of ``x_g`` turns those slots into zeros without any
    branching.  Shapes: x_G (N_G,), l2g_ext (E, m^3) -> (E, m^3).
    """
    padded = jnp.concatenate([x_g, jnp.zeros((1,), x_g.dtype)])
    return jnp.take(padded, l2g_ext, axis=0)


def gather_masked(y_l: jax.Array, l2g_ext: jax.Array, n_global: int) -> jax.Array:
    """Z_sᵀ y_L for maps with a dummy slot: out-of-domain entries dropped.

    The transpose of :func:`scatter_masked` — contributions indexed
    ``n_global`` land in the dummy segment and are sliced away, so the
    pair stays an exact (adjoint) scatter/gather pair for the PCG-symmetry
    argument.  Shapes: y_L (E, m^3), l2g_ext (E, m^3) -> (N_G,).
    """
    return jax.ops.segment_sum(
        y_l.reshape(-1), l2g_ext.reshape(-1), num_segments=n_global + 1
    )[:n_global]


def inverse_degree(l2g: np.ndarray, n_global: int) -> np.ndarray:
    """Global inverse-degree vector diag(Z^T Z)^{-1} as numpy float64."""
    counts = np.zeros((n_global,), dtype=np.float64)
    np.add.at(counts, l2g.reshape(-1), 1.0)
    return 1.0 / counts


def local_inverse_degree(l2g: np.ndarray, n_global: int) -> np.ndarray:
    """W in scattered layout: (E, p) inverse multiplicity of each local node.

    Satisfies Z^T W Z = I; this is the weight hipBone fuses into the operator
    kernel (λW term) and NekBone uses for weighted inner products.
    """
    inv = inverse_degree(l2g, n_global)
    return inv[l2g]
