"""Distributed hipBone: the screened Poisson operator over a device mesh.

The global element grid is block-partitioned over a 3-D process grid mapped
onto the (flattened) device mesh — each rank owns a box of elements plus a
*padded, consistent* assembled-DOF box (interface points replicated across
sharing ranks, every replica holding the true value). See DESIGN.md §5.

Operator application follows the paper's Fig. 2 communication-hiding split:

    halo elements first                 y_h = (S_L + λW) Z_h x_box
    local gather of halo contributions  box_h = Z_h^T y_h
    ── sum_exchange(box_h) ──╮          (async collective...)
    interior elements        │          y_i = (S_L + λW) Z_i x_box   ...overlaps
    local gather             │          box_i = Z_i^T y_i             this compute
    ─────────────────────────╯
    combine                             A x = exchanged(box_h) + box_i

Z and Z^T are the rank's halo and interior ``gather_scatter.assembly``:
the lattice pair on sub-boxes of the padded box — six face slabs for the
halo, the core for the interior (``_element_blocks``) — where the rank's
map is that lattice, else XLA's take and segment sum through ``l2g``.  The
same split applies a Galerkin level's block matvec (``_split_apply``), and
the smoother diagonal and pMG transfers are ``core.precond``'s, completed
by the level's sum-exchange.

Interior elements touch no rank-boundary points, so their contributions
commute with the exchange — that is exactly why the split hides the
communication. Because the padded storage keeps replicas consistent, one
sum-exchange does the work of hipBone's two phases (halo + gather); the
paper-faithful two-phase dataflow is available as ``two_phase=True`` for
comparison.

Inner products mask out replica slots (each interface DOF counted once),
then ``psum`` — the assembled-storage analogue of the paper's observation
that hipBone needs no weighted inner products.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comms import plan as xplan
from ..comms.halo import (
    contract_exchange,
    copy_exchange,
    expand_exchange,
    sum_exchange,
)
from ..comms.topology import ProcessGrid
from ..compat import shard_map
from .. import obs
from . import sem
from .coefficients import coefficient_fields
from .mesh import normalize_bc
from .cg import (
    CG_VARIANTS,
    DIVERGENCE_FACTOR,
    STAGNATION_RTOL,
    STAGNATION_WINDOW,
    _pcg,
)
from .galerkin import block_matvec_einsum, galerkin_ladder_blocks
from .gather_scatter import assembly, indexed_assembly
from .geometry import geometric_factors_from_coords
from .operator import COARSE_K_FLOOR, local_poisson, screen_stream
from .precond import (
    CHEB_LMIN_SAFETY,
    CHEB_SAFETY,
    PMG_SMOOTHERS,
    PRECOND_KINDS,
    SCHWARZ_INNER_DEGREE,
    assembled_diagonal,
    cast_apply,
    chebyshev_apply,
    chebyshev_apply_deferred,
    jacobi_apply,
    lanczos_extremes,
    level_assembly,
    make_transfer_pair,
    make_vcycle,
    make_vcycle_overlapped,
    pmg_degree_ladder,
    pmg_smooth_degree_default,
    power_lambda_max,
    seed_values,
    smoother_interval,
)
from .schwarz import (
    SchwarzFDM,
    build_fdm,
    element_lengths,
    element_neighbor_flags,
    fdm_solve,
    overlap_counts_1d,
)


# TPU's default f32 matmul is one bf16 pass; the solver needs full f32
_HI = jax.lax.Precision.HIGHEST

__all__ = [
    "DistPoisson",
    "box_global_indices",
    "build_dist_problem",
    "build_pmg_levels",
    "build_pmg_galerkin_blocks",
    "dist_cg",
    "dist_cg_scattered",
    "dist_lambda_max",
    "dist_solver",
    "dist_spectrum",
]

# dist_cg's supported coarse-operator constructions: the chained "galerkin"
# stays single-device (its recursive fine applies would serialize the whole
# transfer chain through every rank); the materialized "galerkin_mat" is the
# sharded-capable form — per-rank blocks, standard sum-exchange at apply.
PMG_COARSE_OPS_DIST = ("redisc", "galerkin_mat")

# (routing, wire_dtype) pair threaded from the ExchangePlan into each halo
# primitive call; the default is the historical per-dim face sweep at the
# native wire
_XCH = ("face_sweep", None)


@dataclasses.dataclass(frozen=True)
class DistPoisson:
    """Sharded screened-Poisson problem state.

    Static (identical on every rank): l2g, halo_elems, d, lam, box_shape,
    grid. Sharded data (leading axis = ranks): g, w_local, mask, and the
    solution/rhs vectors (P, m3).
    """

    grid: ProcessGrid
    axis_name: Any               # mesh axis name (or tuple) the ranks live on
    n_degree: int
    local_shape: tuple[int, int, int]    # elements per rank (bx, by, bz)
    box_shape: tuple[int, int, int]      # padded DOF box (bx*N+1, ...)
    lam: float
    halo_elems: int              # elements [0:Eh] touch the rank boundary
    l2g: np.ndarray              # (E_loc, p) int32, same on all ranks
    d: jax.Array                 # (n1, n1)
    g: jax.Array                 # (R, E_loc, 6, p) sharded
    w_local: jax.Array           # (R, E_loc, p) sharded — global inverse degree
    mask: jax.Array              # (R, m3) sharded — 1 where rank owns the DOF
    dtype: Any
    # (R, E_loc, p, 3) numpy node coords in halo-first element order, kept so
    # p-multigrid can rediscretize coarse levels on the same curved geometry;
    # None for the regular unit-box mesh (coarse factors are then analytic)
    coords: np.ndarray | None = None
    regular: bool = True         # True iff built from the default regular mesh
    # variable-coefficient state.  k / lam_field are (R, E_loc, p) numpy
    # setup copies in the same halo-first element order (p-multigrid
    # resamples them per coarse level; Schwarz takes element means); k is
    # already folded into ``g`` at build time.  ``screen`` is the sharded
    # runtime stream JW·λ(x) that replaces ``(w_local, lam)`` in every
    # A-apply when present — the weak mass screen with the kernels' static
    # ``lam`` pinned to 1.0, mirroring ``core.operator.screen_stream``.
    # ``bc_mask`` is the sharded replica-consistent 0/1 Dirichlet mask over
    # padded-box slots (None when no face is Dirichlet).
    k: np.ndarray | None = None
    lam_field: np.ndarray | None = None
    screen: jax.Array | None = None
    bc: tuple | None = None
    bc_mask: jax.Array | None = None

    @property
    def m3(self) -> int:
        return int(np.prod(self.box_shape))

    @property
    def e_local(self) -> int:
        return int(np.prod(self.local_shape))

    @property
    def n_global(self) -> int:
        n = self.n_degree
        gx = self.grid.shape[0] * self.local_shape[0] * n + 1
        gy = self.grid.shape[1] * self.local_shape[1] * n + 1
        gz = self.grid.shape[2] * self.local_shape[2] * n + 1
        return gx * gy * gz


def _local_node_offsets(n: int, pad: int = 0) -> tuple[np.ndarray, ...]:
    """Flattened (t, s, r)-ordered local node offsets [-pad, n + pad]."""
    a = np.arange(-pad, n + pad + 1)
    la, lb, lc = np.meshgrid(a, a, a, indexing="ij")
    return (
        la.transpose(2, 1, 0).reshape(-1),
        lb.transpose(2, 1, 0).reshape(-1),
        lc.transpose(2, 1, 0).reshape(-1),
    )


def _element_blocks(
    local_shape: tuple[int, int, int],
) -> tuple[list[tuple[tuple[int, int, int], tuple[int, int, int]]], int]:
    """The rank's element box as sub-lattices: halo blocks first, then the core.

    Each block is ``(origin, shape)`` in elements along (x, y, z).  With
    every side at least 3 the halo is six face slabs, in this order: z-low
    and z-high ``(bx, by, 1)``, y-low and y-high ``(bx, 1, bz-2)``, x-low
    and x-high ``(1, by-2, bz-2)``; the interior is the
    ``(bx-2, by-2, bz-2)`` core.  With a side of 2 or less every element
    touches a face: the whole box is one halo block and there is no core.
    Returns ``(blocks, number of halo blocks)``.
    """
    bx, by, bz = local_shape
    if min(local_shape) <= 2:
        return [((0, 0, 0), (bx, by, bz))], 1
    z_slab, y_slab, x_slab = (bx, by, 1), (bx, 1, bz - 2), (1, by - 2, bz - 2)
    halo = [
        ((0, 0, 0), z_slab), ((0, 0, bz - 1), z_slab),
        ((0, 0, 1), y_slab), ((0, by - 1, 1), y_slab),
        ((0, 1, 1), x_slab), ((bx - 1, 1, 1), x_slab),
    ]
    return halo + [((1, 1, 1), (bx - 2, by - 2, bz - 2))], len(halo)


def _ordered_elements(local_shape: tuple[int, int, int]) -> tuple[np.ndarray, int]:
    """Halo-first local element coordinates: (E_loc, 3) int array + halo count.

    Elements on any face of the rank's local box come first — their
    operator contributions feed the halo exchange, and their Schwarz blocks
    are the only ones reading the expanded-box shells, so the same ordering
    drives both communication-hiding splits.  The order is that of
    ``_element_blocks``, each block in its own lattice order (x fastest),
    so that each block's elements are one lattice Z/Zᵀ apart from its
    points (``_split_assemblies``).
    """
    blocks, n_halo_blocks = _element_blocks(local_shape)
    parts = []
    for (i0, j0, k0), (ex, ey, ez) in blocks:
        k, j, i = np.meshgrid(
            np.arange(ez), np.arange(ey), np.arange(ex), indexing="ij"
        )
        parts.append(np.stack([i0 + i, j0 + j, k0 + k], axis=-1).reshape(-1, 3))
    n_halo = sum(int(np.prod(shape)) for _, shape in blocks[:n_halo_blocks])
    return np.concatenate(parts).astype(np.int64), n_halo


def _local_l2g(n: int, local_shape: tuple[int, int, int]) -> tuple[np.ndarray, int]:
    """Halo-first element ordering + local node -> padded-box flat map."""
    bx, by, bz = local_shape
    mx, my = bx * n + 1, by * n + 1
    loc_a, loc_b, loc_c = _local_node_offsets(n)
    ordered, n_halo = _ordered_elements(local_shape)

    gx = ordered[:, 0, None] * n + loc_a[None, :]
    gy = ordered[:, 1, None] * n + loc_b[None, :]
    gz = ordered[:, 2, None] * n + loc_c[None, :]
    return (gx + mx * (gy + my * gz)).astype(np.int32), n_halo


def _rank_data(
    grid: ProcessGrid,
    n: int,
    local_shape: tuple[int, int, int],
    l2g: np.ndarray,
) -> tuple[np.ndarray, np.ndarray]:
    """Per-rank (mask, w_local) arrays, stacked over ranks (numpy)."""
    bx, by, bz = local_shape
    px, py, pz = grid.shape
    mx, my, mz = bx * n + 1, by * n + 1, bz * n + 1
    gx_n, gy_n, gz_n = px * bx * n, py * by * n, pz * bz * n  # global max index

    def axis_count(g: np.ndarray, gmax: int) -> np.ndarray:
        """Number of elements sharing a global grid line index."""
        return np.where((g % n == 0) & (g > 0) & (g < gmax), 2, 1)

    masks, ws = [], []
    x = np.arange(mx)
    y = np.arange(my)
    z = np.arange(mz)
    for r in range(grid.size):
        ci, cj, ck = grid.coords(r)
        gx = ci * bx * n + x
        gy = cj * by * n + y
        gz = ck * bz * n + z
        # ownership: not on a low face that has a -neighbor
        own_x = (x > 0) | (ci == 0)
        own_y = (y > 0) | (cj == 0)
        own_z = (z > 0) | (ck == 0)
        mask = (
            own_x[:, None, None] & own_y[None, :, None] & own_z[None, None, :]
        )
        # mask grid is (x, y, z) but flat box index is x + mx*(y + my*z)
        mask_flat = mask.transpose(2, 1, 0).reshape(-1)  # z slow -> matches
        cx = axis_count(gx, gx_n)
        cy = axis_count(gy, gy_n)
        cz = axis_count(gz, gz_n)
        count = (
            cx[:, None, None] * cy[None, :, None] * cz[None, None, :]
        ).transpose(2, 1, 0).reshape(-1)
        w_box = 1.0 / count
        ws.append(w_box[l2g])          # scatter to element-local layout
        masks.append(mask_flat.astype(np.float64))
    return np.stack(masks), np.stack(ws)


def _regular_box_coords(
    grid: ProcessGrid, n: int, local_shape: tuple[int, int, int]
) -> np.ndarray:
    """(R, E_loc, p, 3) node coords of the regular unit-box global mesh.

    Evaluates the *same* per-axis node formula as ``mesh.build_box_mesh``
    on the global element grid, then gathers each rank's halo-first
    elements — so coefficient fields sampled here are bitwise identical to
    the single-device mesh's, which is what the sharded-vs-single
    iteration-parity tests rely on.
    """
    gll, _ = sem.gll_nodes_weights(n)
    bx, by, bz = local_shape
    px, py, pz = grid.shape

    def axis_nodes(ne: int) -> np.ndarray:
        h = 1.0 / ne
        pos = np.empty(ne * n + 1)
        for e in range(ne):
            pos[e * n : (e + 1) * n + 1] = (e + (gll + 1.0) / 2.0) * h
        return pos

    pxn, pyn, pzn = axis_nodes(px * bx), axis_nodes(py * by), axis_nodes(pz * bz)
    ordered, _ = _ordered_elements(local_shape)
    loc_a, loc_b, loc_c = _local_node_offsets(n)
    e_loc = bx * by * bz
    out = np.empty((grid.size, e_loc, (n + 1) ** 3, 3))
    for r in range(grid.size):
        ci, cj, ck = grid.coords(r)
        gx = (ordered[:, 0] + ci * bx)[:, None] * n + loc_a[None, :]
        gy = (ordered[:, 1] + cj * by)[:, None] * n + loc_b[None, :]
        gz = (ordered[:, 2] + ck * bz)[:, None] * n + loc_c[None, :]
        out[r] = np.stack([pxn[gx], pyn[gy], pzn[gz]], axis=-1)
    return out


def _box_dirichlet_mask(
    grid: ProcessGrid,
    n: int,
    local_shape: tuple[int, int, int],
    tags: tuple[str, ...] | None,
) -> np.ndarray | None:
    """(R, m3) 0/1 Dirichlet mask over padded-box slots, or None.

    The sharded twin of ``mesh.dirichlet_mask``: purely topological on the
    structured *global* node grid, so replica slots on different ranks get
    identical values by construction and mesh deformation does not move
    the mask.  Returns None when no face is Dirichlet (Neumann faces are
    natural in the weak form).
    """
    if tags is None or all(t == "neumann" for t in tags):
        return None
    bx, by, bz = local_shape
    px, py, pz = grid.shape
    mx, my, mz = bx * n + 1, by * n + 1, bz * n + 1
    gx_n, gy_n, gz_n = px * bx * n, py * by * n, pz * bz * n  # global max idx
    x, y, z = np.meshgrid(
        np.arange(mx), np.arange(my), np.arange(mz), indexing="ij"
    )
    out = np.empty((grid.size, mx * my * mz))
    for r in range(grid.size):
        ci, cj, ck = grid.coords(r)
        ix, iy, iz = ci * bx * n + x, cj * by * n + y, ck * bz * n + z
        keep = np.ones(x.shape, dtype=bool)
        for tag, sel in zip(
            tags,
            (ix == 0, ix == gx_n, iy == 0, iy == gy_n, iz == 0, iz == gz_n),
        ):
            if tag == "dirichlet":
                keep &= ~sel
        out[r] = keep.transpose(2, 1, 0).reshape(-1).astype(np.float64)
    return out


def build_dist_problem(
    n_degree: int,
    grid: ProcessGrid,
    local_shape: tuple[int, int, int],
    *,
    axis_name: Any = "ranks",
    lam: float = 1.0,
    dtype: Any = jnp.float32,
    g_factors: np.ndarray | None = None,
    coords: np.ndarray | None = None,
    coefficient: str | None = None,
    bc: Any = None,
    k: np.ndarray | None = None,
    lam_field: np.ndarray | None = None,
    mesh: jax.sharding.Mesh | None = None,
) -> DistPoisson:
    """Build the sharded screened-Poisson problem.

    Args:
      n_degree: SEM polynomial degree N.
      grid: (px, py, pz) process grid over the flattened device mesh.
      local_shape: (bx, by, bz) elements owned per rank.
      axis_name: mesh axis name the ranks live on.
      lam: screen parameter λ.
      dtype: runtime dtype of the sharded arrays.
      g_factors: optional (R, E_loc, 6, p) geometric factors in halo-first
        element order (tests pass factors extracted from a deformed global
        mesh); default is the regular unit-box mesh where every element is
        identical.
      coords: optional (R, E_loc, p, 3) node coordinates in the same
        halo-first element order — geometric factors are then computed
        here, and p-multigrid (``dist_cg(precond="pmg")``) can
        rediscretize its coarse levels on the same geometry (with bare
        ``g_factors`` there is no geometry to coarsen, so pmg requires
        either ``coords`` or the default regular mesh).  The Schwarz
        preconditioner also reads ``coords`` for its per-element
        directional lengths (regular meshes use the analytic spacing).
      coefficient: named coefficient family (``core.coefficients``) —
        evaluates k(x) / λ(x) on this mesh's node coordinates (regular
        meshes synthesize them analytically); ``"const"``/``None`` is the
        legacy constant-λ problem, bit-identical code paths.
      bc: boundary-condition spec (``mesh.normalize_bc`` forms) — Dirichlet
        faces produce the replica-consistent ``bc_mask``; Neumann faces
        are natural and need no treatment.
      k / lam_field: explicit (R, E_loc, p) per-quadrature-point fields in
        halo-first element order (p-multigrid passes resampled coarse
        fields; tests pass fields partitioned from a single-device
        problem).  Mutually exclusive with ``coefficient``.  k is folded
        multiplicatively into the packed geometric factors here — kernels
        never see it; λ(x) switches every A-apply to the weak mass screen
        ``JW·λ`` riding the w stream (``DistPoisson.screen``), which needs
        node coordinates (or the regular mesh) for the JW weights.
      mesh: optional device mesh of the ranks.  Given, every per-rank array
        is uploaded laid out over it (rank r's slab on rank r's device),
        the layout the solvers place them in, so that no device ever holds
        every rank's arrays; otherwise they land on the default device.

    Returns:
      A :class:`DistPoisson`; per-rank padded box shape is
      ``(bx·N+1, by·N+1, bz·N+1)`` with interface replicas.
    """
    n = n_degree
    bx, by, bz = local_shape
    with obs.span("setup.build_dist_problem"):
        with obs.span("setup.dist.rank_data"):
            l2g, halo = _local_l2g(n, local_shape)
            mask, w_local = _rank_data(grid, n, local_shape, l2g)

            e_loc = bx * by * bz
            p = (n + 1) ** 3
            regular = g_factors is None and coords is None
            jw = None
            if coords is not None:
                geo = geometric_factors_from_coords(
                    coords.reshape(grid.size * e_loc, p, 3), n
                )
                jw = geo["JW"].reshape(grid.size, e_loc, p)
                if g_factors is None:
                    g_factors = geo["G"].reshape(grid.size, e_loc, 6, p)
            if g_factors is None:
                # regular mesh: every element congruent; element size = 1/(P_d*b_d)
                from .geometry import geometric_factors
                from .mesh import build_box_mesh

                ref_mesh = build_box_mesh(
                    n,
                    (1, 1, 1),
                    extent=(
                        1.0 / (grid.shape[0] * bx),
                        1.0 / (grid.shape[1] * by),
                        1.0 / (grid.shape[2] * bz),
                    ),
                )
                geo_one = geometric_factors(ref_mesh)
                g_one = geo_one["G"][0]  # (6, p)
                g_factors = np.broadcast_to(
                    g_one, (grid.size, e_loc, 6, g_one.shape[-1])
                )
                jw = np.broadcast_to(geo_one["JW"][0], (grid.size, e_loc, p))

            if coefficient is not None:
                if k is not None or lam_field is not None:
                    raise ValueError(
                        "pass either coefficient= or explicit k/lam_field, not both"
                    )
                node_coords = coords
                if node_coords is None:
                    if not regular:
                        raise ValueError(
                            "coefficient evaluation needs node coordinates; pass "
                            "coords= alongside bare g_factors"
                        )
                    node_coords = _regular_box_coords(grid, n, local_shape)
                k, lam_field = coefficient_fields(
                    coefficient, node_coords.reshape(grid.size * e_loc, p, 3), lam
                )
                if k is not None:
                    k = k.reshape(grid.size, e_loc, p)
                if lam_field is not None:
                    lam_field = lam_field.reshape(grid.size, e_loc, p)

            if k is not None:
                k = np.asarray(k, np.float64)
                if k.shape != (grid.size, e_loc, p):
                    raise ValueError(
                        f"k must have shape {(grid.size, e_loc, p)}, got {k.shape}"
                    )
                # fold k into the packed factors: DᵀGD then discretizes -∇·(k∇·)
                g_factors = np.asarray(g_factors) * k[:, :, None, :]
            screen = None
            if lam_field is not None:
                lam_field = np.asarray(lam_field, np.float64)
                if lam_field.shape != (grid.size, e_loc, p):
                    raise ValueError(
                        f"lam_field must have shape {(grid.size, e_loc, p)}, "
                        f"got {lam_field.shape}"
                    )
                if jw is None:
                    raise ValueError(
                        "lam_field needs node coordinates (or the regular mesh) to "
                        "form the JW mass weights of the weak screen; pass coords="
                    )
                screen = np.asarray(jw) * lam_field

            tags = normalize_bc(bc)
            bc_mask = _box_dirichlet_mask(grid, n, local_shape, tags)

            d = sem.derivative_matrix(n)
        with obs.span("setup.dist.upload"):
            if mesh is None:
                upload = functools.partial(jnp.asarray, dtype=dtype)
            else:
                ranks = jax.sharding.NamedSharding(mesh, P(axis_name))

                def upload(a):
                    return jax.device_put(np.asarray(a, dtype), ranks)

            return DistPoisson(
                grid=grid,
                axis_name=axis_name,
                n_degree=n,
                local_shape=local_shape,
                box_shape=(bx * n + 1, by * n + 1, bz * n + 1),
                lam=float(lam),
                halo_elems=halo,
                l2g=l2g,
                d=jnp.asarray(d, dtype),
                g=upload(g_factors),
                w_local=upload(w_local),
                mask=upload(mask),
                dtype=dtype,
                coords=coords,
                regular=regular,
                k=k,
                lam_field=lam_field,
                screen=None if screen is None else upload(screen),
                bc=tags,
                bc_mask=None if bc_mask is None else upload(bc_mask),
            )


def build_pmg_levels(
    prob: DistPoisson, ladder: tuple[int, ...] | None = None
) -> tuple[list[DistPoisson], list[np.ndarray]]:
    """The p-multigrid hierarchy for a sharded problem.

    Args:
      prob: the fine-level :class:`DistPoisson`.
      ladder: explicit degree ladder; default ``pmg_degree_ladder`` halving.

    Returns ``(levels, jmats)``: ``levels[0] is prob`` and each coarser
    level is a full DistPoisson on the *same* process grid and element
    partition (so every level's operator reuses the Fig. 2
    communication-hiding split on its own, smaller padded box);
    ``jmats[i]`` is the 1-D coarse->fine interpolation between levels
    i+1 and i.  Coarse geometric factors are rediscretized from sampled
    coordinates (curved meshes) or the analytic regular-box reference.
    """
    degrees = tuple(ladder) if ladder is not None else pmg_degree_ladder(
        prob.n_degree
    )
    if not prob.regular and prob.coords is None:
        raise ValueError(
            "pmg on a sharded problem needs per-rank coords (or the default "
            "regular mesh) to rediscretize coarse levels; rebuild with "
            "build_dist_problem(..., coords=...)"
        )
    levels = [prob]
    jmats: list[np.ndarray] = []
    for nc in degrees[1:]:
        pf = levels[-1]
        coords_c = None
        if pf.coords is not None:
            jc = sem.interpolation_matrix(pf.n_degree, nc)
            r, e_loc, p, _ = pf.coords.shape
            coords_c = sem.interp_coords_3d(
                jc, pf.coords.reshape(r * e_loc, p, 3)
            ).reshape(r, e_loc, (nc + 1) ** 3, 3)
        # coefficient fields ride down by the same tensor interpolation as
        # the coordinates, with the same fixed positivity floors as the
        # single-device ``operator.coarsen_problem`` — value-for-value
        # identical resampling rank by rank
        k_c = lam_c = None
        if pf.k is not None or pf.lam_field is not None:
            jf = sem.interpolation_matrix(pf.n_degree, nc)
            r, e_loc = prob.grid.size, pf.e_local
            if pf.k is not None:
                k_c = np.maximum(
                    sem.interp_field_3d(
                        jf, np.asarray(pf.k, np.float64).reshape(r * e_loc, -1)
                    ),
                    COARSE_K_FLOOR,
                ).reshape(r, e_loc, -1)
            if pf.lam_field is not None:
                lam_c = np.maximum(
                    sem.interp_field_3d(
                        jf,
                        np.asarray(pf.lam_field, np.float64).reshape(
                            r * e_loc, -1
                        ),
                    ),
                    0.0,
                ).reshape(r, e_loc, -1)
        levels.append(
            build_dist_problem(
                nc,
                prob.grid,
                prob.local_shape,
                axis_name=prob.axis_name,
                lam=prob.lam,
                dtype=prob.dtype,
                coords=coords_c,
                k=k_c,
                lam_field=lam_c,
                bc=pf.bc,
            )
        )
        jmats.append(sem.interpolation_matrix(nc, pf.n_degree))
    return levels, jmats


def build_pmg_galerkin_blocks(
    prob: DistPoisson, levels: list[DistPoisson]
) -> list[jax.Array]:
    """Per-rank materialized Galerkin blocks for every coarse pMG level.

    The sharded face of ``core.galerkin``: each dense element block
    ``Ĵᵀ(S_L^e + λW_e)Ĵ`` reads only the owning rank's geometric factors
    and inverse-degree weights — and ``w_local`` already carries the
    *global* inverse degree (cross-rank sharing accounted for at
    ``_rank_data`` time) — so assembly of the owned coarse elements is
    embarrassingly rank-local on the padded box: **no setup exchange**.
    Apply time then needs only the standard sum-exchange of halo-element
    contributions (``_split_apply``), identical in shape to any
    rediscretized level's.

    Fields are cast to ``prob.dtype`` first, so a mixed-precision caller
    (``dist_cg(precond_dtype=jnp.float32)`` passes its cast problem view)
    assembles the blocks once in fp32 behind the usual cast boundary.

    Args:
      prob: the fine-level :class:`DistPoisson` (or its cast view).
      levels: the ``build_pmg_levels`` hierarchy (``levels[0] is prob``).

    Returns:
      One ``(R, E_loc, p_c, p_c)`` sharded block stack per coarse level
      ``levels[1:]``.
    """
    r, e_loc = prob.g.shape[:2]
    degrees = tuple(lvl.n_degree for lvl in levels)
    # variable λ(x): the screen stream JW·λ replaces (w_local, λ) in the
    # element blocks — Ĵᵀ(S_L^e + diag(JW·λ))Ĵ
    w_src, lam_eff = screen_stream(prob)

    def build(g: jax.Array, w: jax.Array) -> list[jax.Array]:
        g2 = g.astype(prob.dtype).reshape(r * e_loc, *g.shape[2:])
        w2 = w.astype(prob.dtype).reshape(r * e_loc, -1)
        blocks = galerkin_ladder_blocks(g2, prob.d, lam_eff, w2, degrees)
        return [b.reshape(r, e_loc, *b.shape[1:]) for b in blocks]

    if not isinstance(prob.g, jax.Array):
        # dry-run lowering passes abstract ShapeDtypeStruct shards; give the
        # compiled program matching abstract block operands
        return list(jax.eval_shape(build, prob.g, w_src))
    return build(prob.g, w_src)


def _exchange(fn: Callable, prob: DistPoisson, box: jax.Array, pick: tuple,
              site: str) -> jax.Array:
    """``fn`` (``comms.halo``'s sum or copy exchange) of one rank's flat
    padded box, with the exchange plan's (routing, wire) ``pick``, under
    the scope ``halo.site.<site>``."""
    with obs.scope(f"halo.site.{site}"):
        return fn(
            box.reshape(prob.box_shape[::-1]), prob.grid, prob.axis_name,
            pick[1], pick[0],
        ).reshape(-1)


def _split_assemblies(prob: DistPoisson) -> tuple:
    """``(halo, interior)``: the ``gather_scatter.assembly`` of the rank's
    halo and interior elements from and into its flat padded box, the
    lattice pair on ``_element_blocks``' face slabs and core where the
    rank's map is that lattice (``_local_l2g``'s), else indexed."""
    blocks, n_halo_blocks = _element_blocks(prob.local_shape)
    eh = prob.halo_elems
    return (
        assembly(prob.l2g[:eh], prob.n_degree, prob.local_shape,
                 blocks[:n_halo_blocks]),
        assembly(prob.l2g[eh:], prob.n_degree, prob.local_shape,
                 blocks[n_halo_blocks:]),
    )


def _split_kind(prob: DistPoisson) -> str:
    """``"lattice"`` when both halves of the rank's split are, else ``"indexed"``."""
    kinds = {asm.kind for asm in _split_assemblies(prob)}
    return "lattice" if kinds == {"lattice"} else "indexed"


def _split_apply(
    prob: DistPoisson,
    kernel: Callable[[jax.Array, slice], jax.Array],
    *,
    two_phase: bool,
    xsum: tuple = _XCH,
    xcopy: tuple = _XCH,
    site: str = "operator",
) -> Callable[..., jax.Array]:
    """One rank's A-apply on its consistent padded box, with the Fig. 2
    overlap split.

    ``kernel(u, rows)`` is the element operator on the element-local field
    ``u`` of the elements ``rows``: ``local_poisson`` on the rank's ``g`` /
    ``w`` rows (``_rank_operator``), or a Galerkin level's batched block
    matvec on its block rows.  Halo elements go first and their Zᵀ feeds
    the sum-exchange; interior elements touch no rank-boundary point, so
    their work overlaps that exchange.  Z and Zᵀ are
    ``_split_assemblies``'.  ``two_phase`` first refreshes the replicas
    with an explicit copy exchange (the paper's two-phase dataflow).
    ``xsum``/``xcopy`` are the exchange plan's (routing, wire) picks for
    this level's sites, scoped ``halo.site.<site>``.

    The returned ``apply(x_box, x_raw=None)`` takes an optional deferred
    twin ``x_raw`` (the same box *before* its producing sum-exchange): the
    interior reads it instead — bitwise identical, since the exchange only
    rewrites face slabs interior elements never touch — which releases the
    interior block from the upstream exchange's data dependence
    (cross-level V-cycle overlap).
    """
    eh = prob.halo_elems
    asm_h, asm_i = _split_assemblies(prob)

    def block(asm, x: jax.Array, rows: slice) -> jax.Array:
        with obs.scope("op.scatter"):
            u = asm.z(x)
        with obs.scope("op.local"):
            y = kernel(u, rows)
        with obs.scope("op.gather"):
            return asm.zt(y)

    def apply(x_box: jax.Array, x_raw: jax.Array | None = None) -> jax.Array:
        if two_phase:
            x_box = _exchange(copy_exchange, prob, x_box, xcopy, site)
            x_raw = None  # the refreshed box is the only valid source
        box_h = _exchange(
            sum_exchange, prob, block(asm_h, x_box, slice(None, eh)), xsum, site
        )
        if prob.e_local == eh:
            return box_h
        # interior elements: no boundary contact -> overlaps the exchange above
        x_int = x_box if x_raw is None else x_raw
        return box_h + block(asm_i, x_int, slice(eh, None))

    return apply


def _bc_wrap(bc_mask: jax.Array | None, f: Callable) -> Callable:
    """mask∘f∘mask on the Dirichlet subspace — the operator (congruence
    keeps it SPD there) and every preconditioner ingredient, mirroring the
    single-device ``poisson_assembled`` / ``precond._mask_wrap`` contract.
    The optional deferred raw twin is masked too: the mask is elementwise
    and the exchange only rewrites face slabs, so the masked raw stays a
    bitwise-valid interior gather source."""
    if bc_mask is None:
        return f

    def wrapped(v, raw=None):
        if raw is None:
            return bc_mask * f(bc_mask * v)
        return bc_mask * f(bc_mask * v, bc_mask * raw)

    return wrapped


def _on_rank(
    prob: DistPoisson,
    g: jax.Array,
    w: jax.Array,
    mask: jax.Array | None,
    screen: jax.Array | None = None,
    bc_mask: jax.Array | None = None,
) -> DistPoisson:
    """One rank's view of a level inside shard_map: its ``g``, ``w_local``,
    ``mask``, ``screen`` and ``bc_mask`` are that rank's shards, so that
    ``operator.screen_stream`` and ``core.precond``'s level forms read it
    as they read a single-device problem."""
    return dataclasses.replace(
        prob, g=g, w_local=w, mask=mask, screen=screen, bc_mask=bc_mask
    )


def _rank_operator(
    rank: DistPoisson,
    *,
    local_op: Callable[..., jax.Array],
    two_phase: bool,
    xsum: tuple = _XCH,
    xcopy: tuple = _XCH,
) -> Callable[..., jax.Array]:
    """A level's A-apply on one rank (a ``_on_rank`` view): the Fig. 2
    split of ``local_op`` with the exchange plan's picks, on the screen
    stream (the weak mass screen JW·λ(x) with ``lam`` pinned to 1.0 when
    present), wrapped as mask∘A∘mask when ``rank.bc_mask`` is given.
    ``dist_solver``'s Krylov operator and ``solve.operator`` are both this
    one construction."""
    w_eff, lam_eff = screen_stream(rank)

    def kernel(u: jax.Array, rows: slice) -> jax.Array:
        return local_op(u, rank.g[rows], rank.d, lam_eff, w_eff[rows])

    return _bc_wrap(rank.bc_mask, _split_apply(
        rank, kernel, two_phase=two_phase, xsum=xsum, xcopy=xcopy
    ))


def _level_assembly(prob: DistPoisson):
    """``precond.level_assembly`` of all the rank's elements on its padded box."""
    blocks, _ = _element_blocks(prob.local_shape)
    return level_assembly(prob.l2g, prob.n_degree, prob.local_shape, blocks)


def _rank_dinv(rank: DistPoisson, xsum: tuple = _XCH) -> jax.Array:
    """Inverse assembled diagonal of one rank's level (a ``_on_rank`` view)
    in consistent padded-box storage: ``precond.assembled_diagonal``
    through the rank's level assembly, completed by one sum-exchange.  The
    diagonal itself stays unmasked; on Dirichlet problems its inverse is
    multiplied by the bc mask, mirroring ``precond.masked_dinv``."""
    diag = _exchange(
        sum_exchange, rank, assembled_diagonal(rank, _level_assembly(rank)),
        xsum, "diag",
    )
    return 1.0 / diag if rank.bc_mask is None else rank.bc_mask * (1.0 / diag)


def _completion(prob: DistPoisson, xsum: tuple, site: str) -> Callable:
    """A pMG transfer's completion on one level of a rank: its locally
    summed box ``raw`` -> ``(raw, consistent)``, the consistent box after
    the level's sum-exchange.  Raw interior slots are already final."""
    return lambda raw: (raw, _exchange(sum_exchange, prob, raw, xsum, site))


def _masked_dot(mask: jax.Array) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """This rank's share of a dot product: each replicated slot counted by
    its owner only (``mask``) or weighted (``w``), to be psum'd."""
    return lambda a, b: jnp.vdot(a * mask, b, precision=_HI)


def _aux_operands(prob: DistPoisson) -> tuple:
    """The fine level's optional sharded arrays: (screen?, bc_mask?)."""
    return tuple(x for x in (prob.screen, prob.bc_mask) if x is not None)


def _aux_split(prob: DistPoisson, aux_s: tuple) -> tuple:
    """This rank's (screen, bc_mask) out of ``_aux_operands``' shards."""
    s1 = aux_s[0][0] if prob.screen is not None else None
    bcm1 = aux_s[1 if s1 is not None else 0][0] if prob.bc_mask is not None else None
    return s1, bcm1


def _exchange_plan(
    mesh: jax.sharding.Mesh,
    prob: DistPoisson,
    sites: list,
    *,
    exchange: str | None,
    wire: str,
    plan: Any,
):
    """The exchange plan of one solver, under ``setup.exchange_plan``.

    A given ``plan`` is used as it is; otherwise it is resolved for
    ``sites``.  Each site is counted under its route (``xch.route.*``).
    """
    with obs.span("setup.exchange_plan"):
        if plan is None:
            plan = xplan.build_exchange_plan(
                mesh, prob.grid, prob.axis_name, sites,
                policy=exchange, wire=wire,
            )
        xplan.tally_routes(plan, sites)
    return plan


def _place(mesh: jax.sharding.Mesh, spec: P, tree: Any) -> Any:
    """Concrete arrays of ``tree`` laid out over ``mesh`` by ``spec`` (leading
    axis over the ranks); abstract shapes (dry-run lowering) pass through."""
    sharding = jax.sharding.NamedSharding(mesh, spec)
    return jax.tree.map(
        lambda a: jax.device_put(a, sharding) if isinstance(a, jax.Array) else a,
        tree,
    )


def _psum(axis_name: str) -> Callable[[jax.Array], jax.Array]:
    """All-reduce of the recurrence scalars over the ranks, under its scope."""

    @obs.scope("cg.allreduce")
    def psum(v: jax.Array) -> jax.Array:
        return lax.psum(v, axis_name)

    return psum


def box_global_indices(prob: DistPoisson) -> np.ndarray:
    """(R, m3) flat *global* DOF index of every padded-box slot (numpy).

    Replica slots on different ranks map to the same global index, so any
    function of this array is automatically replica-consistent.
    """
    n = prob.n_degree
    bx, by, bz = prob.local_shape
    mx, my, mz = prob.box_shape
    px, py, _ = prob.grid.shape
    gx_n, gy_n = px * bx * n + 1, py * by * n + 1
    x, y, z = np.meshgrid(
        np.arange(mx), np.arange(my), np.arange(mz), indexing="ij"
    )
    out = np.empty((prob.grid.size, prob.m3), np.int64)
    for r in range(prob.grid.size):
        ci, cj, ck = prob.grid.coords(r)
        gidx = (ci * bx * n + x) + gx_n * (
            (cj * by * n + y) + gy_n * (ck * bz * n + z)
        )
        out[r] = gidx.transpose(2, 1, 0).reshape(-1)
    return out


@dataclasses.dataclass(frozen=True)
class _SchwarzDist:
    """Setup for the sharded overlapping-Schwarz apply on one level.

    Static (identical on all ranks): the extended local-to-box index maps,
    split halo-first like the operator — interior blocks read the original
    box only (their solves overlap the shell exchange in the XLA dataflow),
    halo blocks read the shell-expanded box.  Sharded (leading axis ranks):
    the per-element FDM factors (rank-boundary flags and deformed-element
    lengths differ per rank) and the partition-of-unity weights.
    """

    overlap: int
    eh: int                      # halo element count (blocks using shells)
    ext_shape: tuple[int, int, int]   # expanded box (mx+2s, my+2s, mz+2s)
    l2g_halo: np.ndarray         # (Eh, m^3) flat indices into expanded box
    l2g_int: np.ndarray          # (E-Eh, m^3) flat indices into original box
    fdm_fields: tuple[jax.Array, ...]   # stacked SchwarzFDM arrays (R, ...)
    wsqrt: jax.Array             # (R, m3) 1/sqrt(overlap counts)
    # float for the legacy algebraic screen; None when a per-element λ
    # array (element means of λ(x), mass-screen mode) rides fdm_fields[6]
    lam: float | None
    inner_degree: int

    def rank_fdm(self, fields: tuple[jax.Array, ...], sl: slice) -> SchwarzFDM:
        """Per-rank SchwarzFDM from shard-sliced field arrays."""
        tm, cm, di, mu, lo, hi = (f[sl] for f in fields[:6])
        lam = self.lam if self.lam is not None else fields[6][sl]
        return SchwarzFDM(
            tmats=tm, cmats=cm, denom_inv=di, musum=mu, inner_lo=lo,
            inner_hi=hi, lam=lam, overlap=self.overlap,
            inner_degree=self.inner_degree,
        )


def _schwarz_setup(
    prob: DistPoisson, overlap: int, inner_degree: int
) -> _SchwarzDist:
    """Numpy setup of the sharded Schwarz smoother for one level.

    Per-element FDM factors use the rank's node coordinates (or the
    analytic regular-mesh spacing) and *global* neighbor flags — a rank
    boundary is interior to the global element grid, so blocks there extend
    across it; only physical domain boundaries clamp.  The extended index
    maps shift every coordinate by the overlap so halo blocks address the
    shell-expanded box.
    """
    n = prob.n_degree
    s = int(overlap)
    if not 0 <= s <= n - 1:
        raise ValueError(f"overlap must be in [0, {n - 1}] for N={n}, got {s}")
    bx, by, bz = prob.local_shape
    px, py, pz = prob.grid.shape
    mx, my, mz = prob.box_shape
    ordered, eh = _ordered_elements(prob.local_shape)
    loc_a, loc_b, loc_c = _local_node_offsets(n, pad=s)

    # extended maps: halo blocks -> expanded box, interior -> original box
    ex_x = ordered[:, 0, None] * n + loc_a[None, :]
    ex_y = ordered[:, 1, None] * n + loc_b[None, :]
    ex_z = ordered[:, 2, None] * n + loc_c[None, :]
    mex, mey, mez = mx + 2 * s, my + 2 * s, mz + 2 * s
    l2g_halo = (
        (ex_x[:eh] + s) + mex * ((ex_y[:eh] + s) + mey * (ex_z[:eh] + s))
    ).astype(np.int32)
    l2g_int = (
        ex_x[eh:] + mx * (ex_y[eh:] + my * ex_z[eh:])
    ).astype(np.int32)

    gshape = (px * bx, py * by, pz * bz)   # global element grid
    regular_lengths = np.array(
        [1.0 / gshape[0], 1.0 / gshape[1], 1.0 / gshape[2]]
    )
    cx = overlap_counts_1d(gshape[0], n, s)
    cy = overlap_counts_1d(gshape[1], n, s)
    cz = overlap_counts_1d(gshape[2], n, s)

    # variable coefficients enter the blocks by per-element means, exactly
    # like the single-device ``schwarz.element_screen_means``: k scales the
    # stiffness eigenvalue sums; a λ(x) field switches the screen to the
    # in-basis-exact mass form with per-element λ riding a 7th field array
    k_means = (
        None if prob.k is None
        else np.asarray(prob.k, np.float64).mean(axis=2)
    )
    lam_means = (
        None if prob.lam_field is None
        else np.asarray(prob.lam_field, np.float64).mean(axis=2)
    )
    screen_mode = "algebraic" if lam_means is None else "mass"

    nfield = 6 if lam_means is None else 7
    fields: list[list[np.ndarray]] = [[] for _ in range(nfield)]
    wsqrt = np.empty((prob.grid.size, prob.m3))
    for r in range(prob.grid.size):
        ci, cj, ck = prob.grid.coords(r)
        eidx = ordered + np.array([ci * bx, cj * by, ck * bz])
        flags = element_neighbor_flags(eidx, gshape)
        if prob.coords is not None:
            lengths = element_lengths(prob.coords[r], n)
        else:
            lengths = np.broadcast_to(regular_lengths, (prob.e_local, 3))
        fdm = build_fdm(
            lengths, flags, n,
            prob.lam if lam_means is None else lam_means[r],
            s, prob.dtype,
            inner_degree=inner_degree,
            k_elem=None if k_means is None else k_means[r],
            screen=screen_mode,
        )
        per_rank = (fdm.tmats, fdm.cmats, fdm.denom_inv, fdm.musum,
                    fdm.inner_lo, fdm.inner_hi)
        if lam_means is not None:
            per_rank = per_rank + (fdm.lam,)
        for f, arr in zip(fields, per_rank):
            f.append(np.asarray(arr))
        counts = (
            cz[ck * bz * n : ck * bz * n + mz][:, None, None]
            * cy[cj * by * n : cj * by * n + my][None, :, None]
            * cx[ci * bx * n : ci * bx * n + mx][None, None, :]
        )
        wsqrt[r] = 1.0 / np.sqrt(counts.reshape(-1))

    return _SchwarzDist(
        overlap=s,
        eh=eh,
        ext_shape=(mex, mey, mez),
        l2g_halo=l2g_halo,
        l2g_int=l2g_int,
        fdm_fields=tuple(jnp.asarray(np.stack(f)) for f in fields),
        wsqrt=jnp.asarray(wsqrt, prob.dtype),
        lam=float(prob.lam) if lam_means is None else None,
        inner_degree=int(inner_degree),
    )


def _box_schwarz_apply(
    prob: DistPoisson,
    sd: _SchwarzDist,
    fdm_fields: tuple[jax.Array, ...],
    wsq: jax.Array,
    *,
    xsum: tuple = _XCH,
    xexpand: tuple = _XCH,
    xcontract: tuple = _XCH,
) -> Callable[[jax.Array], jax.Array]:
    """Per-rank Schwarz application on consistent padded boxes.

    The Fig. 2 split, Schwarz flavor: the shell expansion (ppermutes) is
    launched first, interior blocks solve from the *original* box with no
    data dependence on it (XLA overlaps them with the exchange), halo
    blocks then read the expanded box and their out-of-rank contributions
    ride the contract exchange home.  One final sum-exchange makes the
    interface replicas consistent, exactly like the operator's gather.
    """
    s = sd.overlap
    eh = sd.eh
    asm_h = indexed_assembly(sd.l2g_halo, int(np.prod(sd.ext_shape)))
    asm_i = indexed_assembly(sd.l2g_int, prob.m3)
    fdm = sd.rank_fdm(fdm_fields, slice(None))

    def sub(lo: int, hi: int | None) -> SchwarzFDM:
        return dataclasses.replace(
            fdm,
            tmats=fdm.tmats[lo:hi], cmats=fdm.cmats[lo:hi],
            denom_inv=fdm.denom_inv[lo:hi], musum=fdm.musum[lo:hi],
            inner_lo=fdm.inner_lo[lo:hi], inner_hi=fdm.inner_hi[lo:hi],
            # a per-element (E, 1, 1, 1) λ array must follow the block split
            lam=fdm.lam if isinstance(fdm.lam, float) else fdm.lam[lo:hi],
        )

    fdm_halo, fdm_int = sub(0, eh), sub(eh, None)

    def apply(r_box: jax.Array) -> jax.Array:
        rw = wsq * r_box
        # shell expansion first: halo-block inputs feed on the ppermutes
        with obs.scope("halo.site.schwarz"):
            ext = expand_exchange(
                rw.reshape(prob.box_shape[::-1]), prob.grid, prob.axis_name, s,
                xexpand[1], xexpand[0],
            ).reshape(-1)
        acc = asm_h.zt(fdm_solve(fdm_halo, asm_h.z(ext)))
        with obs.scope("halo.site.schwarz"):
            box = contract_exchange(
                acc.reshape(sd.ext_shape[::-1]), prob.grid, prob.axis_name, s,
                xcontract[1], xcontract[0],
            ).reshape(-1)
        # interior blocks: no shell contact -> overlap the exchanges above
        if eh < prob.e_local:
            box = box + asm_i.zt(fdm_solve(fdm_int, asm_i.z(rw)))
        return wsq * _exchange(sum_exchange, prob, box, xsum, "schwarz")

    return apply


def _exchange_sites(
    prob: DistPoisson,
    levels: list,
    schwarz_setups: list,
    *,
    two_phase: bool = False,
) -> list:
    """Enumerate every halo-exchange site of one dist_cg configuration.

    One ``sum``/``copy`` site per pMG level (level 0 carries the *outer*
    problem dtype — the dominant payload — even when the preconditioner
    chain is cast down), plus ``expand``/``contract`` shell sites for each
    Schwarz-smoothed level.  The tuner groups sites by (kind, box shape,
    dtype, depth), so equal-shaped levels share one measurement.
    """
    box0 = tuple(prob.box_shape[::-1])
    dt0 = jnp.dtype(prob.dtype).name
    sites = [
        xplan.ExchangeSite("sum", 0, box0, dt0),
        xplan.ExchangeSite("copy", 0, box0, dt0),
    ]
    for i, lvl in enumerate(levels[1:], start=1):
        box = tuple(lvl.box_shape[::-1])
        dt = jnp.dtype(lvl.dtype).name
        sites.append(xplan.ExchangeSite("sum", i, box, dt))
        if two_phase:
            sites.append(xplan.ExchangeSite("copy", i, box, dt))
    for i, sd in enumerate(schwarz_setups):
        lvl = levels[i]
        dt = jnp.dtype(lvl.dtype).name
        sites.append(
            xplan.ExchangeSite(
                "expand", i, tuple(lvl.box_shape[::-1]), dt, depth=sd.overlap
            )
        )
        sites.append(
            xplan.ExchangeSite(
                "contract", i, tuple(sd.ext_shape[::-1]), dt, depth=sd.overlap
            )
        )
    return sites


def _estimate(
    prob: DistPoisson,
    mesh: jax.sharding.Mesh,
    estimate: Callable,
    out_specs: Any,
    *,
    local_op: Callable[..., jax.Array] | None,
    two_phase: bool,
):
    """``estimate(A, D⁻¹, seed, dot=, psum=)`` over the ranks, compiled and
    run once: each rank's operator and inverse diagonal are the solver's
    (``_rank_operator``, ``_rank_dinv``), dots are replica-masked and
    psum'd, and the seed is a hash of *global* DOF indices, so consistent
    across replicas; on Dirichlet problems it is zero on the Dirichlet
    slots (mirrors ``precond.masked_seed``: no null-space pollution)."""
    op = local_op or local_poisson
    spec = P(prob.axis_name)
    seed_boxes = jnp.asarray(seed_values(box_global_indices(prob)), prob.dtype)
    if prob.bc_mask is not None:
        seed_boxes = seed_boxes * prob.bc_mask.astype(seed_boxes.dtype)
    aux = _aux_operands(prob)

    def shard_fn(g_s, w_s, mask_s, seed_s, aux_s):
        rank = _on_rank(prob, g_s[0], w_s[0], mask_s[0], *_aux_split(prob, aux_s))
        return estimate(
            _rank_operator(rank, local_op=op, two_phase=two_phase),
            _rank_dinv(rank), seed_s[0],
            dot=_masked_dot(rank.mask), psum=_psum(prob.axis_name),
        )

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, tuple(spec for _ in aux)),
        out_specs=out_specs,
        # check_rep cannot type the Lanczos / power-iteration carries
        # (sharded iterate, replicated psum-derived scalars)
        check_rep=False,
    )
    return jax.jit(fn)(prob.g, prob.w_local, prob.mask, seed_boxes, aux)


def dist_spectrum(
    prob: DistPoisson,
    mesh: jax.sharding.Mesh,
    *,
    lanczos_iters: int = 10,
    local_op: Callable[..., jax.Array] | None = None,
    two_phase: bool = False,
) -> tuple[float, float]:
    """Eager (λ_min, λ_max) Ritz estimates of D⁻¹A (raw, no safety factors).

    The sharded analogue of ``precond.lanczos_extremes``: replica-masked
    dots, psum across ranks.  Pass the results to
    ``dist_cg(..., lmin=..., lmax=...)`` so repeated Chebyshev solves don't
    re-run the estimation inside the compiled program.

    Returns:
      ``(lmin, lmax)`` python floats (the compiled estimate is pulled
      eagerly at setup time).
    """
    lmin, lmax = _estimate(
        prob, mesh, functools.partial(lanczos_extremes, iters=lanczos_iters),
        (P(), P()), local_op=local_op, two_phase=two_phase,
    )
    return float(lmin), float(lmax)


def dist_lambda_max(
    prob: DistPoisson,
    mesh: jax.sharding.Mesh,
    *,
    power_iters: int = 12,
    local_op: Callable[..., jax.Array] | None = None,
    two_phase: bool = False,
) -> float:
    """Eagerly estimate λ_max(D⁻¹A) once at setup time (raw, no safety
    factor).  Pass the result to ``dist_cg(..., lmax=...)`` so repeated
    Chebyshev solves don't re-run the power iteration inside the compiled
    program (keeps benchmark timings pure solve)."""
    return float(_estimate(
        prob, mesh, functools.partial(power_lambda_max, iters=power_iters),
        P(), local_op=local_op, two_phase=two_phase,
    ))


def dist_cg(
    prob: DistPoisson, mesh: jax.sharding.Mesh, b: jax.Array, **kwargs
):
    """Distributed hipBone (P)CG of one right-hand side over the device mesh.

    ``b`` is the (R, m3) sharded right-hand side boxes (made consistent
    inside); every keyword argument is :func:`dist_solver`'s, which runs
    the solve.

    Returns:
      A jitted-callable partial () -> (x, rdotr, iterations, status,
      history): :func:`dist_solver`'s program with ``b`` bound — also
      usable for dry-run lowering via ``jax.jit(run.func).lower(*run.args)``
      — with the resolved plan as ``run.exchange_plan``.  A caller with
      many right-hand sides uses :func:`dist_solver` itself.
    """
    solve = dist_solver(prob, mesh, **kwargs)
    run = functools.partial(solve.program, b, *solve.operands)
    run.exchange_plan = solve.exchange_plan
    return run


def dist_solver(
    prob: DistPoisson,
    mesh: jax.sharding.Mesh,
    *,
    n_iter: int = 100,
    tol: float | None = None,
    precond: str = "none",
    cheb_degree: int = 2,
    lanczos_iters: int = 10,
    lmax: float | None = None,
    lmin: float | None = None,
    pmg_smooth_degree: int | None = None,
    pmg_smoother: str = "chebyshev",
    pmg_coarse_op: str = "redisc",
    pmg_coarse_iters: int = 16,
    pmg_ladder: tuple[int, ...] | None = None,
    schwarz_overlap: int = 1,
    schwarz_inner_degree: int = SCHWARZ_INNER_DEGREE,
    precond_dtype: Any = None,
    cg_variant: str = "standard",
    local_op: Callable[..., jax.Array] | None = None,
    two_phase: bool = False,
    exchange: str | None = None,
    exchange_wire: str = "native",
    exchange_plan: Any = None,
    vcycle_overlap: bool | None = None,
    record_history: bool = False,
    divergence_factor: float | None = DIVERGENCE_FACTOR,
    stagnation_window: int | None = STAGNATION_WINDOW,
    stagnation_rtol: float = STAGNATION_RTOL,
    per_rank_stats: bool = False,
):
    """Distributed hipBone (P)CG over the device mesh, compiled once for
    every right-hand side.

    Time-stepping callers solve many right-hand sides on one operator and
    preconditioner; this is their entry point.  :func:`dist_cg` is the
    same solve of one bound ``b``.

    Args:
      prob: the sharded problem (``build_dist_problem``).
      mesh: jax device mesh whose flattened size equals ``prob.grid.size``.
      n_iter: iteration cap (NekBone's fixed count when ``tol`` is None).
      tol: optional relative-residual stopping threshold (while_loop mode).
      precond: "none" | "jacobi" | "chebyshev" | "schwarz" | "pmg".
      cheb_degree: standalone-Chebyshev polynomial degree.
      lanczos_iters: in-graph Lanczos steps for Chebyshev intervals.
      lmax / lmin: pre-estimated spectrum bounds (from ``dist_spectrum``)
        — passing them keeps the estimation out of the compiled solve;
        ``lmax`` alone falls back to the legacy λ_max/30 interval bottom.
      pmg_smooth_degree: Chebyshev stages per pMG smoothing sweep (default:
        4 for the Jacobi base, 2 for the Schwarz base).
      pmg_smoother: "chebyshev" (Chebyshev–Jacobi) or "schwarz"
        (Chebyshev-accelerated overlapping Schwarz on every smoothed
        level — the nekRS configuration).
      pmg_coarse_op: "redisc" (default) rediscretizes every coarse level;
        "galerkin_mat" applies the variationally-exact PᵀAP coarse
        operators as materialized per-element blocks
        (``build_pmg_galerkin_blocks``): assembly is rank-local at setup
        (no extra exchange — ``w_local`` already carries the global
        inverse degree), and each coarse apply is one batched element
        matvec riding the standard halo/interior split + sum-exchange
        (``_split_apply``) — matching the single-device
        ``make_pmg_preconditioner(coarse_op="galerkin_mat")`` up to the
        order of summation, including under ``precond_dtype``.  The
        *chained* "galerkin" form stays single-device (its coarse applies
        recurse to the fine grid) and raises here.
      pmg_coarse_iters: degree of the coarsest-level full-interval Chebyshev.
      pmg_ladder: explicit degree ladder (default N → ⌈N/2⌉ → … → 1).
      schwarz_overlap / schwarz_inner_degree: overlapping-Schwarz knobs
        (extension width in GLL nodes; in-eigenbasis block-solve degree) for
        ``precond="schwarz"`` and ``pmg_smoother="schwarz"``.
      precond_dtype: compute dtype of the whole preconditioner chain
        (default None = ``prob.dtype``).  With fp32 inside an fp64 solve,
        every preconditioner ingredient — A-applies, diagonals, Schwarz
        FDM fields, every coarse pMG level and transfer — runs on fp32
        boxes, so *all* preconditioner halo payloads (sum/copy/expand/
        contract exchanges, coarse-level included) are fp32 on the wire
        while the outer fp64 recurrence keeps tol=1e-8 reachable.  One
        cast boundary wraps the apply; the outer operator and its halo
        exchange stay fp64.  Pair with ``cg_variant="flexible"``.
      cg_variant: "standard" (Fletcher–Reeves β) or "flexible"
        (Polak–Ribière β; robust when M⁻¹ is only fp32-symmetric — see
        core.cg).
      local_op: optional Pallas element kernel replacing the jnp reference.
      two_phase: paper-faithful two-phase exchange instead of the fused one.
      exchange: halo-exchange policy — "face_sweep" (per-dim sweep, the
        default), "crystal" (staged bidirectional route), "fused"
        (one-round diagonal route), or "auto" (time every candidate per
        exchange *site* at setup and pick winners; persisted, see
        ``comms.plan``).  ``None`` defers to ``HIPBONE_EXCHANGE``.  Every
        routing reproduces the face sweep's IEEE reduction tree
        bit-for-bit at the native wire, so PCG iteration counts are
        identical whatever the policy says.
      exchange_wire: wire-dtype axis of the "auto" search — "native"
        (default; keeps the bit-identity guarantee), "auto" (adds
        fp32-on-the-wire candidates for fp64 boxes; replica-consistent
        but moves rounding points), or a concrete dtype name.
      exchange_plan: inject a pre-built ``comms.plan.ExchangePlan``
        (skips plan resolution entirely — benchmarks reuse one plan
        across solver variants).
      vcycle_overlap: cross-level exchange/compute overlap in the pMG
        V-cycle — coarse-level smoothers and fine-level post-smooth
        residuals start their interior element work from the *raw*
        (pre-exchange) transfer boxes, releasing each level's halo
        exchange to overlap the neighbouring level's compute
        (``precond.make_vcycle_overlapped``; bit-identical by
        construction).  ``None`` defers to ``HIPBONE_VCYCLE_OVERLAP``
        (default on).
      record_history: carry the per-iteration ‖r‖² history buffer.
      divergence_factor / stagnation_window / stagnation_rtol: in-loop
        breakdown-detector knobs (see ``core.cg.SolveStatus``); every
        detector input is one of the already-psum'd recurrence scalars, so
        the failure flag is replica-consistent by construction and all
        ranks exit the tolerance-mode loop on the same iteration with the
        same status — no extra collective rides the loop.
      per_rank_stats: return ``iterations`` and ``status`` as per-rank
        (R,)-sharded arrays instead of replicated scalars — observability
        hook for asserting the lockstep-exit property (the slow halo-
        corruption test uses it); the values are identical across ranks.

    Variable coefficients thread through every rung: a k(x) field is
    already folded into ``prob.g`` at build time (nothing to do here), a
    λ(x) field swaps the weak mass screen ``prob.screen`` onto the w
    stream of every A-apply/diagonal/Galerkin block and switches the
    Schwarz blocks to per-element mean-λ mass screens, and Dirichlet
    faces wrap the operator and every preconditioner ingredient in
    ``prob.bc_mask`` (mask∘f∘mask — SPD on the interior subspace by
    congruence), with spectrum-estimation seeds masked per level.  The
    caller is expected to pass a bc-masked ``b`` (the same contract as
    the single-device ``poisson_assembled`` path).  The solve then runs
    the single-device solve's operator, preconditioner and recurrence,
    including under ``precond_dtype``, but sums its dots in another order
    (per rank, then ``psum``): the two ‖r‖² histories agree to rounding
    over the first iterations, CG's amplification of rounding can part
    them later on a hard system, and a tolerance crossing can then land
    an iteration apart, with solutions that agree to the tolerance.

    The Jacobi diagonal is ``precond.assembled_diagonal`` in padded-box
    storage — local element diagonals gathered with the rank's Zᵀ, then
    made consistent by one sum-exchange — so its apply is a pure
    elementwise scale (replicas stay consistent for free).  Chebyshev
    A-applies reuse the communication-hiding split operator, and the
    Lanczos spectrum estimation runs with replica-masked inner products;
    its seed vector is a hash of *global* DOF indices, hence consistent
    across replicas by construction.

    ``precond="schwarz"`` runs symmetric weighted overlapping Schwarz with
    the overlap transported by ``comms.halo.expand_exchange`` /
    ``contract_exchange`` shells; interior element blocks read only the
    original box, so their solves hide the shell exchange exactly like the
    operator's Fig. 2 split (see ``_box_schwarz_apply``).

    ``precond="pmg"`` runs the degree-ladder V-cycle of ``core.precond``
    with every level's A-apply, transfer, diagonal and Schwarz blocks
    assembled through this rank's *coarsened* padded box (the transfers are
    ``precond.make_transfer_pair``, completed by the level's sum-exchange
    into the ``(raw, consistent)`` pair) — coarse-level
    applies are latency-dominated, so the halo/interior overlap matters
    most there.  The coarsest (degree-1) level is solved by a
    full-interval degree-``pmg_coarse_iters`` Chebyshev.

    Returns:
      ``solve(b_boxes) -> (x, rdotr, iterations, status, history)`` for
      ``(R, m3)`` sharded right-hand side boxes (made consistent inside),
      jitted once: a new ``b`` of the same shape reuses the compiled
      program.  ``status`` is the jit-safe ``core.cg.SolveStatus`` code.
      ``solve.program(b, *solve.operands)`` is the un-jitted shard_map
      program with the problem's sharded arrays (laid out over ``mesh``
      once here) as arguments; ``solve.exchange_plan`` is the resolved
      plan.  ``solve.operator(x_boxes, *solve.operator_operands)`` is the
      un-jitted outer A-apply the solve iterates with (its plan, split,
      ``two_phase`` and ``local_op``) on ``(R, m3)``
      consistent boxes, its operands a subset of ``solve.operands``.
    """
    if precond not in PRECOND_KINDS:
        raise ValueError(f"unknown precond {precond!r}; choose from {PRECOND_KINDS}")
    if pmg_smoother not in PMG_SMOOTHERS:
        raise ValueError(
            f"unknown pmg smoother {pmg_smoother!r}; choose from {PMG_SMOOTHERS}"
        )
    if pmg_coarse_op not in PMG_COARSE_OPS_DIST:
        raise NotImplementedError(
            f"dist_cg pmg_coarse_op={pmg_coarse_op!r}: the chained Galerkin "
            "form is single-device only (make_pmg_preconditioner) — its "
            "coarse applies recurse to the fine grid; use the materialized "
            "'galerkin_mat' for the sharded variationally-exact V-cycle, "
            f"or one of {PMG_COARSE_OPS_DIST}"
        )
    if cg_variant not in CG_VARIANTS:
        raise ValueError(
            f"unknown cg_variant {cg_variant!r}; choose from {CG_VARIANTS}"
        )
    if pmg_smooth_degree is None:
        pmg_smooth_degree = pmg_smooth_degree_default(pmg_smoother)
    op = local_op or local_poisson
    spec = P(prob.axis_name)
    hist_len = n_iter

    # Mixed precision: the preconditioner chain is built from a cast *view*
    # of the problem (pprob) — its d matrix and every coarse pMG level /
    # Schwarz FDM field carry cdtype, so preconditioner boxes (and hence
    # every preconditioner halo payload) live in cdtype end to end.  The
    # fine-level sharded g/w are cast once inside the compiled program.
    cdtype = jnp.dtype(prob.dtype if precond_dtype is None else precond_dtype)
    mixed = cdtype != jnp.dtype(prob.dtype)
    pprob = prob if not mixed else dataclasses.replace(
        prob, d=prob.d.astype(cdtype), dtype=cdtype
    )

    def _masked_seed(lvl: DistPoisson) -> jax.Array:
        """Spectrum-estimation seed for one level, Dirichlet rows zeroed
        (mirrors precond.masked_seed — Lanczos stays on the subspace)."""
        sd = jnp.asarray(seed_values(box_global_indices(lvl)), cdtype)
        if lvl.bc_mask is None:
            return sd
        return sd * lvl.bc_mask.astype(cdtype)

    need_power = (precond == "chebyshev" and lmax is None) or precond == "pmg"
    # the seeds only feed preconditioner spectrum estimation -> cdtype
    seed_boxes = (
        _masked_seed(prob) if need_power
        else jnp.zeros((prob.grid.size, 1), cdtype)
    )

    if precond == "pmg":
        levels, _ = build_pmg_levels(pprob, pmg_ladder)
        # materialized Galerkin: per-rank block assembly at setup (pprob is
        # the cast view when mixed, so blocks are assembled once in cdtype)
        gal_blocks = (
            build_pmg_galerkin_blocks(pprob, levels)
            if pmg_coarse_op == "galerkin_mat"
            else [() for _ in levels[1:]]
        )
        pmg_data = tuple(
            (lvl.g, lvl.w_local, lvl.mask, _masked_seed(lvl), _aux_operands(lvl))
            + ((blk,) if pmg_coarse_op == "galerkin_mat" else ())
            for lvl, blk in zip(levels[1:], gal_blocks)
        )
    else:
        levels, pmg_data = [pprob], ()
    # fine-level optional arrays ride their own conditional tuple
    aux_data = _aux_operands(prob)

    # Schwarz setup: one _SchwarzDist per level that smooths with it —
    # level 0 for the standalone kind (overlap validated like the
    # single-device path), every smoothed level for the Schwarz-smoothed
    # V-cycle (overlap clamped to each level's degree, matching
    # make_pmg_preconditioner).  Sharded FDM fields ride the shard_map
    # arguments; static index maps stay in the closure.
    if precond == "schwarz":
        schwarz_setups = [
            _schwarz_setup(pprob, schwarz_overlap, schwarz_inner_degree)
        ]
    elif precond == "pmg" and pmg_smoother == "schwarz":
        schwarz_setups = [
            _schwarz_setup(
                lvl,
                min(schwarz_overlap, lvl.n_degree - 1),
                schwarz_inner_degree,
            )
            for lvl in levels[:-1]
        ]
    else:
        schwarz_setups = []
    schwarz_data = tuple(
        sd.fdm_fields + (sd.wsqrt,) for sd in schwarz_setups
    )

    # Exchange plan: resolve one (routing, wire) pick per halo site.  A
    # forced policy resolves instantly; "auto" times candidates per site
    # class at first setup and loads the persisted plan afterwards.  The
    # picks are static python strings, so each policy traces to its own
    # compiled program with the chosen ppermute schedule baked in.
    exchange_plan = _exchange_plan(
        mesh, prob,
        _exchange_sites(prob, levels, schwarz_setups, two_phase=two_phase),
        exchange=exchange, wire=exchange_wire, plan=exchange_plan,
    )
    xsum = [exchange_plan.lookup("sum", i) for i in range(len(levels))]
    xcopy = [exchange_plan.lookup("copy", i) for i in range(len(levels))]
    xexp = [
        exchange_plan.lookup("expand", i) for i in range(len(schwarz_setups))
    ]
    xcon = [
        exchange_plan.lookup("contract", i) for i in range(len(schwarz_setups))
    ]
    if vcycle_overlap is None:
        vcycle_overlap = os.environ.get("HIPBONE_VCYCLE_OVERLAP", "1") != "0"

    def outer_operator(rank: DistPoisson):
        """This rank's outer A-apply: the solve's and ``solve.operator``'s."""
        return _rank_operator(
            rank, local_op=op, two_phase=two_phase, xsum=xsum[0], xcopy=xcopy[0]
        )

    def shard_fn(b_s, g_s, w_s, mask_s, seed_s, aux_s, pmg_s, schwarz_s):
        b1, g1, w1, m1 = b_s[0], g_s[0], w_s[0], mask_s[0]
        # make rhs consistent (replicas hold true values)
        b1 = _exchange(copy_exchange, prob, b1, xcopy[0], "rhs")
        rank = _on_rank(prob, g1, w1, m1, *_aux_split(prob, aux_s))
        operator = outer_operator(rank)
        psum = _psum(prob.axis_name)

        # preconditioner-dtype view of the fine level: the casts are single
        # ops reused by every M⁻¹-internal A-apply in the program; every
        # level's operator takes the optional deferred raw twin
        if mixed:
            cast = lambda a: None if a is None else a.astype(cdtype)
            prank = _on_rank(pprob, *map(cast, (
                g1, w1, m1, rank.screen, rank.bc_mask
            )))
            operator_pc = _rank_operator(
                prank, local_op=op, two_phase=two_phase,
                xsum=xsum[0], xcopy=xcopy[0],
            )
        else:
            prank, operator_pc = rank, operator

        def schwarz_apply(i: int, lvl: DistPoisson):
            nf = len(schwarz_setups[i].fdm_fields)
            fields1 = tuple(f[0] for f in schwarz_s[i][:nf])
            return _bc_wrap(lvl.bc_mask, _box_schwarz_apply(
                lvl, schwarz_setups[i], fields1, schwarz_s[i][nf][0],
                xsum=xsum[i], xexpand=xexp[i], xcontract=xcon[i],
            ))

        pc = None
        if precond != "none":
            dinv = _rank_dinv(prank, xsum[0])
            if precond == "jacobi":
                pc = jacobi_apply(dinv)
            elif precond == "schwarz":
                pc = schwarz_apply(0, prank)
            elif precond == "chebyshev":
                if lmax is None:
                    lmin_e, lmax_e = lanczos_extremes(
                        operator_pc, dinv, seed_s[0],
                        iters=lanczos_iters, dot=_masked_dot(prank.mask),
                        psum=psum,
                    )
                    top = CHEB_SAFETY * lmax_e
                    low = CHEB_LMIN_SAFETY * lmin_e
                else:
                    top = CHEB_SAFETY * jnp.asarray(lmax, cdtype)
                    low = None if lmin is None else (
                        CHEB_LMIN_SAFETY * jnp.asarray(lmin, cdtype)
                    )
                pc = chebyshev_apply(
                    operator_pc, dinv, top, lmin=low, degree=cheb_degree
                )
            else:  # pmg
                ranks, lvl_ops, lvl_dinvs = [prank], [operator_pc], [dinv]
                lvl_seeds = [seed_s[0]]
                for li, (lvl, (g_l, w_l, mk_l, sd_l, aux_l, *blk)) in enumerate(
                    zip(levels[1:], pmg_s), start=1
                ):
                    r = _on_rank(
                        lvl, g_l[0], w_l[0], mk_l[0], *_aux_split(lvl, aux_l)
                    )
                    if blk:
                        # materialized P^T A P apply: batched element
                        # matvec + the standard sum-exchange, zero
                        # fine-operator work per coarse apply; the bc wrap
                        # uses this level's own mask (R = Pᵀ smears
                        # interior residual onto coarse Dirichlet rows)
                        lvl_ops.append(_bc_wrap(r.bc_mask, _split_apply(
                            r,
                            lambda u, rows, b=blk[0][0]: block_matvec_einsum(
                                b[rows], u
                            ),
                            two_phase=two_phase, xsum=xsum[li],
                            xcopy=xcopy[li], site="galerkin",
                        )))
                    else:
                        lvl_ops.append(_rank_operator(
                            r, local_op=op, two_phase=two_phase,
                            xsum=xsum[li], xcopy=xcopy[li],
                        ))
                    # smoother diagonals stay the rediscretized ones for
                    # the Galerkin variants, matching the single-device path
                    lvl_dinvs.append(_rank_dinv(r, xsum[li]))
                    ranks.append(r)
                    lvl_seeds.append(sd_l[0])
                # every lvl_ops entry accepts (v, raw=None); the pair form
                # feeds the overlapped V-cycle's deferred interior gathers
                lvl_ops_pair = [
                    (lambda raw, con, f=f: f(con, raw)) for f in lvl_ops
                ]

                smoothers, smoothers_pair = [], []
                for i in range(len(levels) - 1):
                    if pmg_smoother == "schwarz":
                        base = schwarz_apply(i, ranks[i])
                    else:
                        base = lvl_dinvs[i]
                    lo, lmax_e, _ = smoother_interval(
                        lvl_ops[i], base, lvl_seeds[i],
                        smoother=pmg_smoother, lanczos_iters=lanczos_iters,
                        dot=_masked_dot(ranks[i].mask), psum=psum,
                    )
                    smooth = chebyshev_apply(
                        lvl_ops[i],
                        base,
                        CHEB_SAFETY * lmax_e,
                        lmin=lo,
                        degree=pmg_smooth_degree,
                    )
                    smoothers.append(smooth)
                    if pmg_smoother == "schwarz":
                        # Schwarz expand shells transport face values, so
                        # the base apply cannot start from the raw twin
                        smoothers_pair.append(
                            lambda raw, con, sm=smooth: sm(con)
                        )
                    else:
                        smoothers_pair.append(
                            chebyshev_apply_deferred(
                                lvl_ops[i], lvl_ops_pair[i], base,
                                CHEB_SAFETY * lmax_e, lmin=lo,
                                degree=pmg_smooth_degree,
                            )
                        )
                # coarsest (degree-1): full-interval Chebyshev "solve"
                lmin_e, lmax_e = lanczos_extremes(
                    lvl_ops[-1], lvl_dinvs[-1], lvl_seeds[-1],
                    iters=lanczos_iters, dot=_masked_dot(ranks[-1].mask),
                    psum=psum,
                )
                coarse_apply = chebyshev_apply(
                    lvl_ops[-1],
                    lvl_dinvs[-1],
                    CHEB_SAFETY * lmax_e,
                    lmin=CHEB_LMIN_SAFETY * lmin_e,
                    degree=pmg_coarse_iters,
                )
                coarse_apply_pair = chebyshev_apply_deferred(
                    lvl_ops[-1], lvl_ops_pair[-1], lvl_dinvs[-1],
                    CHEB_SAFETY * lmax_e,
                    lmin=CHEB_LMIN_SAFETY * lmin_e,
                    degree=pmg_coarse_iters,
                )
                # the single-device transfers over the ranks' level
                # assemblies, each completed into its (raw, consistent) pair
                prolongs, restricts = [], []
                for i in range(len(levels) - 1):
                    p_up, r_down = make_transfer_pair(
                        ranks[i], ranks[i + 1],
                        _level_assembly(ranks[i]), _level_assembly(ranks[i + 1]),
                        _completion(ranks[i], xsum[i], "prolong"),
                        _completion(ranks[i + 1], xsum[i + 1], "restrict"),
                    )
                    prolongs.append(p_up)
                    restricts.append(r_down)
                if vcycle_overlap:
                    pc = make_vcycle_overlapped(
                        lvl_ops[:-1], lvl_ops_pair[:-1],
                        smoothers, smoothers_pair,
                        restricts, prolongs, coarse_apply_pair,
                    )
                else:
                    pc = make_vcycle(
                        lvl_ops[:-1], smoothers,
                        [lambda r, f=f: f(r)[1] for f in restricts],
                        [lambda z, f=f: f(z)[1] for f in prolongs],
                        coarse_apply,
                    )
        if mixed and pc is not None:
            # the one cast boundary: round r to cdtype, widen z back
            pc = cast_apply(pc, cdtype, b1.dtype)

        res = _pcg(
            operator,
            b1,
            None,
            n_iter=n_iter,
            tol=tol,
            weight=m1,
            psum=psum,
            precond=pc,
            fused_update=None,
            fused_precond_dot=None,
            record_history=record_history,
            variant=cg_variant,
            divergence_factor=divergence_factor,
            stagnation_window=stagnation_window,
            stagnation_rtol=stagnation_rtol,
        )
        hist = res.rdotr_history
        iters = jnp.asarray(res.iterations)
        status = jnp.asarray(res.status)
        if per_rank_stats:
            iters, status = iters[None], status[None]
        return (
            res.x[None],
            res.rdotr,
            iters,
            status,
            hist if hist is not None else jnp.zeros((hist_len,), b1.dtype),
        )

    stat_spec = spec if per_rank_stats else P()
    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            spec, spec, spec, spec, spec,
            jax.tree.map(lambda _: spec, aux_data),
            jax.tree.map(lambda _: spec, pmg_data),
            jax.tree.map(lambda _: spec, schwarz_data),
        ),
        out_specs=(spec, P(), stat_spec, stat_spec, P()),
        # old jax's check_rep has no rule for while_loop (tol mode) and
        # cannot type the Lanczos/power-iteration carries (in-graph spectrum
        # estimation); keep the guard wherever it can actually run — its
        # replicated outputs are psum-derived either way
        check_rep=tol is None and not need_power and precond != "schwarz",
    )
    obs.tally(f"op.assembly.{_split_kind(prob)}")
    operands = _place(mesh, spec, (
        prob.g, prob.w_local, prob.mask, seed_boxes, aux_data, pmg_data,
        schwarz_data,
    ))
    jitted = jax.jit(fn)

    def solve(b_boxes: jax.Array):
        return jitted(b_boxes, *operands)

    def operator_fn(x_s, g_s, w_s, aux_s):
        rank = _on_rank(prob, g_s[0], w_s[0], None, *_aux_split(prob, aux_s))
        return outer_operator(rank)(x_s[0])[None]

    solve.program, solve.operands = fn, operands
    # the A-apply the solve iterates with, on its own: (x_boxes,
    # *solve.operator_operands) -> A x_boxes, the operands a subset of
    # solve.operands (probes time it; tests compare it with the solve's)
    solve.operator = shard_map(
        operator_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec, tuple(spec for _ in aux_data)),
        out_specs=spec,
    )
    solve.operator_operands = (operands[0], operands[1], operands[4])
    # observability: benchmarks/tests read the resolved plan off the handle
    solve.exchange_plan = exchange_plan
    return solve


def dist_cg_scattered(
    prob: DistPoisson,
    mesh: jax.sharding.Mesh,
    b_l: jax.Array,
    *,
    n_iter: int = 100,
    tol: float | None = None,
    precond: str = "none",
    cheb_degree: int = 2,
    lanczos_iters: int = 10,
    lmax: float | None = None,
    lmin: float | None = None,
    precond_dtype: Any = None,
    cg_variant: str = "standard",
    local_op: Callable[..., jax.Array] | None = None,
    exchange: str | None = None,
    exchange_wire: str = "native",
    exchange_plan: Any = None,
    divergence_factor: float | None = DIVERGENCE_FACTOR,
    stagnation_window: int | None = STAGNATION_WINDOW,
    stagnation_rtol: float = STAGNATION_RTOL,
):
    """Distributed NekBone baseline: scattered (R, E_loc, p) vectors.

    Operator: b = ZZ^T S_L x + λ x  (gather-scatter through the padded box
    + sum exchange); weighted inner products read the W stream, exactly the
    extra traffic the paper charges against NekBone.

    Args:
      prob / mesh: as in :func:`dist_cg`.
      b_l: (R, E_loc, p) *consistent* scattered right-hand side (NekBone
        gather-scatters its random forcing at setup; applying ZZ^T here
        would alter a general rhs).
      n_iter / tol / cheb_degree / lanczos_iters / lmax / lmin / local_op:
        as in :func:`dist_cg`.
      precond: "none" | "jacobi" | "chebyshev" — the assembled-only rungs
        (schwarz and p-multigrid live on assembled storage, where block
        solves and transfers are single gathers; the paper's argument for
        assembled storage applies doubly to preconditioning).
      precond_dtype / cg_variant: as in :func:`dist_cg` — an fp32
        Jacobi/Chebyshev chain (scattered fields, gather-scatter boxes and
        their exchanges all in fp32) behind one cast boundary, with the
        flexible (Polak–Ribière) β available for robustness.
      exchange / exchange_wire / exchange_plan: as in :func:`dist_cg` —
        here there is exactly one site, the gather-scatter sum-exchange.

    The assembled diagonal is built in padded-box storage and scattered to
    the element-local layout; on the continuous subspace (range of Z,
    where the scattered iterates live) the diagonal scale and the
    Chebyshev polynomial act exactly as their assembled counterparts, so
    weighted-dot PCG remains valid.

    Returns:
      A jitted-callable partial () -> (x, rdotr, iterations, status) — note
      the 4-tuple, unlike :func:`dist_cg`'s 5-tuple with history.
      ``status`` is the ``core.cg.SolveStatus`` code; the detector knobs
      (``divergence_factor`` / ``stagnation_window`` / ``stagnation_rtol``)
      behave as in :func:`dist_cg`.
    """
    if precond not in ("none", "jacobi", "chebyshev"):
        raise ValueError(
            f"dist_cg_scattered supports none|jacobi|chebyshev, got {precond!r}"
        )
    if prob.lam_field is not None or prob.bc_mask is not None:
        # the scattered baseline mirrors NekBone's constant-λ pure-Neumann
        # problem; a k-folded g is transparent here, but the weak λ(x)
        # screen and Dirichlet masking live on assembled storage only
        raise NotImplementedError(
            "dist_cg_scattered supports only the constant-λ problem without "
            "Dirichlet faces; use dist_cg for variable λ(x) or bc masks"
        )
    if cg_variant not in CG_VARIANTS:
        raise ValueError(
            f"unknown cg_variant {cg_variant!r}; choose from {CG_VARIANTS}"
        )
    op = local_op or local_poisson
    spec = P(prob.axis_name)
    asm = indexed_assembly(prob.l2g, prob.m3)
    cdtype = jnp.dtype(prob.dtype if precond_dtype is None else precond_dtype)
    mixed = cdtype != jnp.dtype(prob.dtype)
    pprob = prob if not mixed else dataclasses.replace(
        prob, d=prob.d.astype(cdtype), dtype=cdtype
    )
    d_pc = pprob.d

    need_lanczos = precond == "chebyshev" and lmax is None
    seed_boxes = jnp.asarray(
        seed_values(box_global_indices(prob)), cdtype
    ) if need_lanczos else jnp.zeros((prob.grid.size, 1), cdtype)

    if exchange_plan is None:
        exchange_plan = xplan.build_exchange_plan(
            mesh, prob.grid, prob.axis_name,
            [
                xplan.ExchangeSite(
                    "sum", 0, tuple(prob.box_shape[::-1]),
                    jnp.dtype(prob.dtype).name,
                )
            ],
            policy=exchange, wire=exchange_wire,
        )
    xs = exchange_plan.lookup("sum", 0)

    def gather_scatter(y_l):
        return asm.z(_exchange(sum_exchange, prob, asm.zt(y_l), xs, "scattered"))

    def shard_fn(b_s, g_s, w_s, seed_s):
        # caller passes a consistent b_L (NekBone gather-scatters its random
        # forcing at setup; applying ZZ^T here would alter a general rhs)
        b1, g1, w1 = b_s[0], g_s[0], w_s[0]
        psum = _psum(prob.axis_name)

        def operator(x_l):
            s = op(x_l, g1, prob.d, 0.0, None)
            return gather_scatter(s) + prob.lam * x_l

        # preconditioner-dtype operator: fp32 local fields, fp32
        # gather-scatter boxes (hence fp32 exchange payloads) when mixed
        if mixed:
            g1c, w1c = g1.astype(cdtype), w1.astype(cdtype)

            def operator_pc(x_l):
                s = op(x_l, g1c, d_pc, 0.0, None)
                return gather_scatter(s) + jnp.asarray(prob.lam, cdtype) * x_l

        else:
            g1c, w1c = g1, w1
            operator_pc = operator

        pc = None
        if precond != "none":
            # assembled diag in box storage, scattered to the local layout:
            # Z diag(A)⁻¹ — consistent on the continuous subspace for free
            dinv_l = asm.z(_rank_dinv(_on_rank(pprob, g1c, w1c, None)))
            if precond == "jacobi":
                pc = jacobi_apply(dinv_l)
            else:
                if lmax is None:
                    lmin_e, lmax_e = lanczos_extremes(
                        operator_pc, dinv_l, asm.z(seed_s[0]),
                        iters=lanczos_iters, dot=_masked_dot(w1c), psum=psum,
                    )
                    top = CHEB_SAFETY * lmax_e
                    low = CHEB_LMIN_SAFETY * lmin_e
                else:
                    top = CHEB_SAFETY * jnp.asarray(lmax, cdtype)
                    low = None if lmin is None else (
                        CHEB_LMIN_SAFETY * jnp.asarray(lmin, cdtype)
                    )
                pc = chebyshev_apply(
                    operator_pc, dinv_l, top, lmin=low, degree=cheb_degree
                )
            if mixed:
                pc = cast_apply(pc, cdtype, b1.dtype)

        res = _pcg(
            operator,
            b1,
            None,
            n_iter=n_iter,
            tol=tol,
            weight=w1,
            psum=psum,
            precond=pc,
            fused_update=None,
            fused_precond_dot=None,
            record_history=False,
            variant=cg_variant,
            divergence_factor=divergence_factor,
            stagnation_window=stagnation_window,
            stagnation_rtol=stagnation_rtol,
        )
        return (
            res.x[None],
            res.rdotr,
            jnp.asarray(res.iterations),
            jnp.asarray(res.status),
        )

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, P(), P(), P()),
        # same check_rep caveats as dist_cg: while_loop (tol mode) and the
        # Lanczos carry have no replication rule on old jax
        check_rep=tol is None and not need_lanczos,
    )
    run = functools.partial(fn, b_l, prob.g, prob.w_local, seed_boxes)
    run.exchange_plan = exchange_plan
    return run
