"""Structured hexahedral SEM mesh with global (assembled) DOF numbering.

NekBone/hipBone use a regular box mesh of ``E = ex*ey*ez`` hexahedral
elements with a degree-N GLL node grid per element. Nodes on shared
faces/edges/corners are the same global degree of freedom; the local-to-
global map encodes the boolean scatter matrix Z (one nonzero per row).

This module is pure numpy setup code; runtime arrays are produced once
and handed to jax.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import sem

__all__ = [
    "BC_FACES",
    "BoxMesh",
    "build_box_mesh",
    "dirichlet_mask",
    "lattice_l2g",
    "normalize_bc",
    "partition_elements",
]

# face order of a boundary-condition 6-tuple (matches the element-grid axes)
BC_FACES = ("x_lo", "x_hi", "y_lo", "y_hi", "z_lo", "z_hi")
_BC_TAGS = ("dirichlet", "neumann")


def normalize_bc(bc) -> tuple[str, ...] | None:
    """Canonicalize a boundary-condition spec to a 6-face tag tuple.

    Accepts ``None`` (legacy: no essential BCs — the operator is the pure
    screened-Poisson A = S + λ·screen on all DOFs), a shorthand string
    (``"dirichlet"`` / ``"neumann"`` on all six faces, or ``"mixed"`` =
    Dirichlet on the two x-faces, Neumann on y/z), or a 6-sequence of
    per-face tags in :data:`BC_FACES` order.  Neumann faces are *natural*
    in the weak form — they need no DOF treatment — so an all-Neumann spec
    produces no mask, only metadata.
    """
    if bc is None:
        return None
    if isinstance(bc, str):
        if bc == "dirichlet":
            return ("dirichlet",) * 6
        if bc == "neumann":
            return ("neumann",) * 6
        if bc == "mixed":
            return ("dirichlet", "dirichlet") + ("neumann",) * 4
        raise ValueError(
            f"unknown bc shorthand {bc!r}; use 'dirichlet'|'neumann'|'mixed' "
            "or a 6-tuple of per-face tags"
        )
    tags = tuple(bc)
    if len(tags) != 6:
        raise ValueError(
            f"bc must name all 6 faces {BC_FACES}, got {len(tags)} entries"
        )
    for face, tag in zip(BC_FACES, tags):
        if tag not in _BC_TAGS:
            raise ValueError(f"bc[{face}] = {tag!r}; choose from {_BC_TAGS}")
    return tags


def dirichlet_mask(mesh: "BoxMesh", bc) -> np.ndarray | None:
    """(N_G,) 0/1 mask: 0 on Dirichlet-face DOFs, 1 elsewhere.

    The mask is topological — it reads the structured global grid index
    (``gx = ex*N + 1`` points per axis, x fastest, exactly the layout
    :func:`build_box_mesh` assigns), so mesh deformation does not move it.
    Returns ``None`` when no face is Dirichlet (nothing to mask: Neumann
    faces are natural).  Operators apply it as A_m = mask∘A∘mask and
    preconditioners as mask∘M∘mask — both SPD on the interior subspace by
    congruence — with homogeneous values carried by lifting (see
    ``repro.testing.mms``).
    """
    tags = normalize_bc(bc)
    if tags is None or all(t == "neumann" for t in tags):
        return None
    n = mesh.n_degree
    ex, ey, ez = mesh.shape
    gx, gy, gz = ex * n + 1, ey * n + 1, ez * n + 1
    if gx * gy * gz != mesh.n_global:
        raise ValueError(
            "dirichlet_mask needs the structured box numbering: "
            f"{gx}*{gy}*{gz} != n_global={mesh.n_global}"
        )
    g = np.arange(mesh.n_global)
    ix = g % gx
    iy = (g // gx) % gy
    iz = g // (gx * gy)
    keep = np.ones(mesh.n_global, dtype=bool)
    for tag, sel in zip(
        tags,
        (ix == 0, ix == gx - 1, iy == 0, iy == gy - 1, iz == 0, iz == gz - 1),
    ):
        if tag == "dirichlet":
            keep &= ~sel
    return keep.astype(np.float64)


@dataclasses.dataclass(frozen=True)
class BoxMesh:
    """A structured SEM box mesh.

    Attributes:
      n_degree: polynomial degree N.
      shape: (ex, ey, ez) element counts per axis.
      l2g: int32 (E, (N+1)^3) local-node -> global-DOF map (the matrix Z).
      coords: float64 (E, (N+1)^3, 3) physical coordinates of local nodes.
      n_global: number of assembled DOFs N_G.
      n_local: number of element-local nodes N_L = E (N+1)^3.
    """

    n_degree: int
    shape: tuple[int, int, int]
    l2g: np.ndarray
    coords: np.ndarray
    n_global: int
    n_local: int

    @property
    def n_elements(self) -> int:
        return int(np.prod(self.shape))

    @property
    def points_per_element(self) -> int:
        return (self.n_degree + 1) ** 3


def lattice_l2g(shape: tuple[int, int, int], n_degree: int) -> np.ndarray:
    """The box mesh's local-to-global map: the global point lattice.

    Global points form the lattice ``(ez*N+1, ey*N+1, ex*N+1)``, x fastest.
    Local node (a, b, c) of element (i, j, k) sits at lattice point
    (i*N + a, j*N + b, k*N + c). Elements are numbered i + ex*(j + ey*k) and
    local nodes a + (N+1)*(b + (N+1)*c), r fastest. Returns (E, p) int32.
    """
    ex, ey, ez = (int(s) for s in shape)
    n = int(n_degree)
    gx, gy = ex * n + 1, ey * n + 1

    def axis(ne: int) -> np.ndarray:  # (element, local node) -> lattice index
        return np.arange(ne)[:, None] * n + np.arange(n + 1)[None, :]

    l2g = (
        gx * gy * axis(ez)[:, None, None, :, None, None]
        + gx * axis(ey)[None, :, None, None, :, None]
        + axis(ex)[None, None, :, None, None, :]
    )  # (k, j, i, c, b, a)
    return l2g.reshape(ex * ey * ez, (n + 1) ** 3).astype(np.int32)


def build_box_mesh(
    n_degree: int,
    shape: tuple[int, int, int],
    *,
    extent: tuple[float, float, float] = (1.0, 1.0, 1.0),
    deform: float = 0.0,
    seed: int = 0,
) -> BoxMesh:
    """Build a structured box mesh on [0, Lx] x [0, Ly] x [0, Lz].

    Args:
      n_degree: SEM polynomial degree N.
      shape: element grid (ex, ey, ez).
      extent: box side lengths.
      deform: if nonzero, apply a smooth sinusoidal coordinate deformation of
        this relative amplitude. The deformation is a diffeomorphism of the
        box (conforming: shared nodes stay shared), producing dense metric
        tensors G^e with all six independent entries nonzero — used by tests
        to exercise the full operator. ``deform=0`` reproduces the regular
        NekBone mesh (diagonal G).
      seed: phase seed for the deformation.

    Returns:
      BoxMesh with local-to-global map and node coordinates.
    """
    ex, ey, ez = (int(s) for s in shape)
    n = int(n_degree)
    if min(ex, ey, ez) < 1:
        raise ValueError(f"element grid must be positive, got {shape}")
    gll, _ = sem.gll_nodes_weights(n)

    # Global point grid: (ex*N + 1, ey*N + 1, ez*N + 1), x fastest.
    gx, gy, gz = ex * n + 1, ey * n + 1, ez * n + 1
    n_global = gx * gy * gz

    # 1-D global node positions per axis (GLL points tiled across elements).
    def axis_nodes(ne: int, length: float) -> np.ndarray:
        h = length / ne
        pos = np.empty(ne * n + 1, dtype=np.float64)
        for e in range(ne):
            pos[e * n : (e + 1) * n + 1] = (e + (gll + 1.0) / 2.0) * h
        return pos

    px = axis_nodes(ex, extent[0])
    py = axis_nodes(ey, extent[1])
    pz = axis_nodes(ez, extent[2])

    # Local-to-global map and each local node's lattice point per axis.
    l2g = lattice_l2g((ex, ey, ez), n)
    gxi, gyj, gzk = l2g % gx, l2g // gx % gy, l2g // (gx * gy)

    coords = np.stack(
        [px[gxi], py[gyj], pz[gzk]], axis=-1
    )  # (E, p, 3) float64

    if deform:
        rng = np.random.default_rng(seed)
        phase = rng.uniform(0, 2 * np.pi, size=(3,))
        lx, ly, lz = extent
        x, y, z = coords[..., 0], coords[..., 1], coords[..., 2]
        amp = deform * min(extent) / (2 * np.pi)
        sx = np.sin(2 * np.pi * x / lx + phase[0])
        sy = np.sin(2 * np.pi * y / ly + phase[1])
        sz = np.sin(2 * np.pi * z / lz + phase[2])
        coords = coords + amp * np.stack(
            [sy * sz, sx * sz, sx * sy], axis=-1
        )

    e_total = ex * ey * ez
    return BoxMesh(
        n_degree=n,
        shape=(ex, ey, ez),
        l2g=l2g,
        coords=coords,
        n_global=n_global,
        n_local=e_total * (n + 1) ** 3,
    )


def partition_elements(
    shape: tuple[int, int, int], grid: tuple[int, int, int]
) -> np.ndarray:
    """Owner rank for each element of a box mesh under a block partition.

    The element grid ``shape`` is split into ``grid = (px, py, pz)`` near-equal
    boxes; rank ordering matches element ordering (x fastest). Returns an
    int32 array of shape (E,) with the owning rank of each element.
    """
    ex, ey, ez = shape
    px, py, pz = grid
    if ex % px or ey % py or ez % pz:
        raise ValueError(f"element grid {shape} not divisible by process grid {grid}")

    ei, ej, ek = np.meshgrid(
        np.arange(ex), np.arange(ey), np.arange(ez), indexing="ij"
    )
    ei = ei.transpose(2, 1, 0).reshape(-1)
    ej = ej.transpose(2, 1, 0).reshape(-1)
    ek = ek.transpose(2, 1, 0).reshape(-1)
    ri = ei // (ex // px)
    rj = ej // (ey // py)
    rk = ek // (ez // pz)
    return (ri + px * (rj + py * rk)).astype(np.int32)
