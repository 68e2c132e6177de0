"""The screened Poisson operator A = S + λI in both storage modes.

hipBone (assembled) mode — paper's central contribution:
    y_L = (S_L + λW) Z x_G        (single fused kernel)
    A x_G = Z^T y_L               (gather; all MPI lives here + halo)

NekBone (scattered) baseline mode:
    b_L = (Z Z^T S_L + λ I) x_L   (combined gather-scatter after local op)

The element-local stiffness is the tensor-product SEM Laplacian
    S_L^e = D^T G^e D
with D the 3-D gradient stack of the 1-D derivative matrix. This module is
the pure-jnp reference implementation; ``repro.kernels`` provides the
Pallas TPU kernel with identical semantics (validated against this).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import coefficients as _coef
from . import geometry, sem
from .gather_scatter import assembly, gather_scatter, inverse_degree
from .mesh import BoxMesh, build_box_mesh, dirichlet_mask, normalize_bc


# TPU's default f32 matmul is one bf16 pass; the solver needs full f32
_HI = jax.lax.Precision.HIGHEST

__all__ = [
    "local_poisson",
    "local_operator_columns",
    "PoissonProblem",
    "build_problem",
    "problem_from_mesh",
    "coarsen_problem",
    "cast_problem",
    "poisson_assembled",
    "poisson_scattered",
    "screen_stream",
]

# positivity floor applied when coefficient fields are resampled to a
# coarser degree: polynomial interpolation of rough (random) fields can
# overshoot below zero, which would break the SPD-ness every V-cycle level
# relies on.  A fixed constant (not data-dependent) so the single-device
# and sharded coarsening paths produce identical values rank by rank.
COARSE_K_FLOOR = 1e-6


def local_poisson(
    u: jax.Array,
    g: jax.Array,
    d: jax.Array,
    lam: jax.Array | float,
    w: jax.Array | None,
    jw: jax.Array | None = None,
) -> jax.Array:
    """Element-local screened Poisson action  (S_L + λ M) u  (pure jnp).

    Args:
      u:  (E, p) element-local field, p = (N+1)^3, node order (t, s, r).
      g:  (E, 6, p) packed geometric factors [rr, rs, rt, ss, st, tt].
      d:  (N+1, N+1) 1-D derivative matrix.
      lam: screen parameter λ.
      w:  (E, p) inverse-degree weights for the hipBone fused form
          (λW screen on assembled DOFs), or None for plain λI (NekBone
          scattered form applies λ to x_L directly).
      jw: (E, p) mass diagonal J*w_q. When given, the screen term is
          λ·(JW∘W)·u (resp. λ·JW·u) — the proper SEM mass-weighted screen.
          NekBone uses the unweighted algebraic screen λI; pass None to
          match NekBone exactly (benchmarks do).

    Returns:
      (E, p) result.
    """
    e, p = u.shape
    n1 = d.shape[0]
    u3 = u.reshape(e, n1, n1, n1)  # (E, t, s, r)

    # Gradient: three batched contractions — these hit the MXU.
    ur = jnp.einsum("ia,etsa->etsi", d, u3, precision=_HI)
    us = jnp.einsum("jb,etbr->etjr", d, u3, precision=_HI)
    ut = jnp.einsum("kc,ecsr->eksr", d, u3, precision=_HI)

    g3 = g.reshape(e, 6, n1, n1, n1)
    wr = g3[:, 0] * ur + g3[:, 1] * us + g3[:, 2] * ut
    ws = g3[:, 1] * ur + g3[:, 3] * us + g3[:, 4] * ut
    wt = g3[:, 2] * ur + g3[:, 4] * us + g3[:, 5] * ut

    # Divergence: transposed contractions.
    out = (
        jnp.einsum("ia,etsi->etsa", d, wr, precision=_HI)
        + jnp.einsum("jb,etjr->etbr", d, ws, precision=_HI)
        + jnp.einsum("kc,eksr->ecsr", d, wt, precision=_HI)
    ).reshape(e, p)

    screen = u if jw is None else jw * u
    if w is not None:
        screen = w * screen
    return out + lam * screen


def local_operator_columns(
    g: jax.Array,
    d: jax.Array,
    lam: jax.Array | float,
    w: jax.Array | None,
    cols: jax.Array,
) -> jax.Array:
    """Element-local operator applied to a stack of shared probe columns.

    Each column of ``cols`` is broadcast to every element and pushed through
    :func:`local_poisson`, so the result materializes the element-local
    operator restricted to the probed subspace — the workhorse of
    :mod:`core.galerkin`'s setup-time block assembly, where ``cols`` holds
    the lifted coarse basis Ĵ.  Columns are swept sequentially
    (``lax.map``): setup-time memory stays one element-local field per
    probe instead of a (k × E × p) temporary blow-up.

    Args:
      g / d / lam / w: as in :func:`local_poisson`.
      cols: (p, k) probe columns, p = (N+1)³.

    Returns:
      (E, p, k) with ``out[e, :, k] = (S_L^e + λ·screen_e) cols[:, k]``.
    """
    e = g.shape[0]

    def apply_col(c: jax.Array) -> jax.Array:
        return local_poisson(jnp.broadcast_to(c, (e, c.shape[0])), g, d, lam, w)

    return jnp.moveaxis(jax.lax.map(apply_col, cols.T), 0, 2)


@dataclasses.dataclass(frozen=True)
class PoissonProblem:
    """A ready-to-run screened Poisson problem (single shard).

    All arrays are jnp in the runtime dtype; setup metadata stays numpy.
    """

    mesh: BoxMesh
    lam: float
    d: jax.Array            # (N+1, N+1)
    g: jax.Array            # (E, 6, p) — k(x) already folded in when set
    jw: jax.Array           # (E, p) mass diagonal
    l2g: jax.Array          # (E, p) int32
    w_local: jax.Array      # (E, p) inverse degree (scattered layout)
    w_global: jax.Array     # (N_G,) inverse degree (assembled layout)
    dtype: Any
    # variable-coefficient / boundary-condition extension — all None for
    # the legacy constant-λ screened Poisson (bit-identical code paths):
    k: jax.Array | None = None          # (E, p) diffusion field (unfolded copy)
    lam_field: jax.Array | None = None  # (E, p) screen field λ(x)
    mask: jax.Array | None = None       # (N_G,) 0 on Dirichlet DOFs
    bc: tuple | None = None             # 6-face tags (mesh.BC_FACES order)

    @property
    def n_global(self) -> int:
        return self.mesh.n_global

    @property
    def n_local(self) -> int:
        return self.mesh.n_local

    @property
    def n_degree(self) -> int:
        return self.mesh.n_degree

    @property
    def screen(self) -> jax.Array | None:
        """The weak mass screen JW·λ(x), or None with no λ(x) field."""
        return None if self.lam_field is None else self.jw * self.lam_field


def screen_stream(prob) -> tuple[jax.Array | None, float]:
    """The (w, lam) pair every element kernel consumes for the screen term.

    Classic mode (``prob.screen is None``): ``(w_local, λ)`` — the algebraic
    λ·W screen that assembles to exactly λI (hipBone/NekBone semantics;
    bit-identical to pre-coefficient builds).

    PDE mode (a λ(x) field): ``(JW·λ_field, 1.0)`` — the mass-weighted
    weak screen Zᵀ diag(JW·λ) Z.  No inverse-degree factor enters: the
    element-wise assembly sum IS the quadrature sum.  ``lam`` stays a
    static python float either way, which is what lets the variable screen
    ride the existing ``w`` stream through kernels whose ``lam`` is a
    static argname (``kernels.poisson`` / ``kernels.poisson_fused``).

    ``prob`` is a :class:`PoissonProblem` or a sharded
    ``distributed.DistPoisson``, which carries the same ``screen``,
    ``w_local`` and ``lam``.
    """
    screen = prob.screen
    return (prob.w_local, prob.lam) if screen is None else (screen, 1.0)


def _eval_field(spec, coords: np.ndarray) -> np.ndarray | None:
    """Evaluate a coefficient spec on the mesh's (E, p, 3) node array.

    ``spec`` may be None, a scalar, a callable f(x, y, z) -> (E, p), or a
    ready (E, p) array.
    """
    if spec is None:
        return None
    if callable(spec):
        out = spec(coords[..., 0], coords[..., 1], coords[..., 2])
        return np.broadcast_to(np.asarray(out), coords.shape[:2])
    arr = np.asarray(spec)
    if arr.ndim == 0:
        return np.full(coords.shape[:2], float(arr))
    if arr.shape != coords.shape[:2]:
        raise ValueError(
            f"coefficient field shape {arr.shape} != (E, p) {coords.shape[:2]}"
        )
    return arr


@obs.spanned("setup.build_problem")
def build_problem(
    n_degree: int,
    shape: tuple[int, int, int],
    *,
    lam: float = 1.0,
    deform: float = 0.0,
    dtype: Any = jnp.float32,
    coefficient: str | None = None,
    bc: Any = None,
) -> PoissonProblem:
    """Construct mesh, geometric factors and gather-scatter data.

    ``coefficient`` selects a named family from ``core.coefficients``
    (``"const"``/None keeps the legacy constant-λ screen bit-identical;
    ``"smooth"``/``"checker"`` switch to A = -∇·(k∇) + λ with the weak
    mass-weighted screen).  ``bc`` is a boundary-condition spec accepted
    by ``mesh.normalize_bc`` (None = legacy, no essential BCs).
    """
    with obs.span("setup.mesh"):
        m = build_box_mesh(n_degree, shape, deform=deform)
        k, lam_field = _coef.coefficient_fields(coefficient, m.coords, lam)
    return problem_from_mesh(
        m, lam=lam, dtype=dtype, k=k, lam_field=lam_field, bc=bc
    )


def problem_from_mesh(
    m: BoxMesh,
    *,
    lam: float = 1.0,
    dtype: Any = jnp.float32,
    k: Any = None,
    lam_field: Any = None,
    bc: Any = None,
) -> PoissonProblem:
    """Geometric factors + gather-scatter data for an existing mesh.

    ``k`` / ``lam_field`` accept None, a scalar, an (E, p) array, or a
    callable f(x, y, z) evaluated on the mesh nodes.  ``k`` is folded into
    the packed geometric factors here — every downstream consumer (local
    kernels, diagonals, Galerkin probes, Schwarz means, sharded boxes)
    sees variable diffusion through the ``g`` stream it already reads.
    """
    with obs.span("setup.geometry"):
        geo = geometry.geometric_factors(m)
        d = sem.derivative_matrix(m.n_degree)
        w_g = inverse_degree(m.l2g, m.n_global)
        w_l = w_g[m.l2g]
        g = np.asarray(geo["G"])
        k_arr = _eval_field(k, m.coords)
        lam_arr = _eval_field(lam_field, m.coords)
        if k_arr is not None:
            g = g * k_arr[:, None, :]
        tags = normalize_bc(bc)
        mask = None if tags is None else dirichlet_mask(m, tags)
    with obs.span("setup.upload"):
        return PoissonProblem(
            mesh=m,
            lam=float(lam),
            d=jnp.asarray(d, dtype=dtype),
            g=jnp.asarray(g, dtype=dtype),
            jw=jnp.asarray(geo["JW"], dtype=dtype),
            l2g=jnp.asarray(m.l2g),
            w_local=jnp.asarray(w_l, dtype=dtype),
            w_global=jnp.asarray(w_g, dtype=dtype),
            dtype=dtype,
            k=None if k_arr is None else jnp.asarray(k_arr, dtype=dtype),
            lam_field=(
                None if lam_arr is None else jnp.asarray(lam_arr, dtype=dtype)
            ),
            mask=None if mask is None else jnp.asarray(mask, dtype=dtype),
            bc=tags,
        )


def coarsen_problem(prob: PoissonProblem, n_coarse: int) -> PoissonProblem:
    """p-coarsened problem: same element grid, polynomial degree ``n_coarse``.

    The coarse level is a *rediscretization*, not a Galerkin triple product:
    element connectivity comes from a degree-``n_coarse`` box mesh, node
    coordinates are the fine (polynomial) coordinate map sampled at the
    coarse GLL nodes — exact, so the coarse operator lives on the same
    curved geometry — and geometric factors are recomputed at the coarse
    degree.  This is the standard SEM p-multigrid coarse operator
    (Nek5000/RS, libParanumal).
    """
    mf = prob.mesh
    nc = int(n_coarse)
    if not 1 <= nc < mf.n_degree:
        raise ValueError(
            f"coarse degree must be in [1, {mf.n_degree - 1}], got {nc}"
        )
    base = build_box_mesh(nc, mf.shape)  # connectivity only; coords replaced
    j = sem.interpolation_matrix(mf.n_degree, nc)
    coords = sem.interp_coords_3d(j, mf.coords)
    mesh_c = dataclasses.replace(base, coords=coords)
    # coefficient fields ride to the coarse level by the same tensor
    # interpolation as the coordinates (exact on the per-element-constant
    # checker family, spectrally accurate on smooth ones); k keeps a fixed
    # positivity floor so every rediscretized level stays SPD, and the
    # Dirichlet mask is recomputed from the bc tags on the coarse grid.
    k_c = lam_c = None
    if prob.k is not None:
        k_c = np.maximum(
            sem.interp_field_3d(j, np.asarray(prob.k, np.float64)),
            COARSE_K_FLOOR,
        )
    if prob.lam_field is not None:
        lam_c = np.maximum(
            sem.interp_field_3d(j, np.asarray(prob.lam_field, np.float64)),
            0.0,
        )
    return problem_from_mesh(
        mesh_c, lam=prob.lam, dtype=prob.dtype, k=k_c, lam_field=lam_c,
        bc=prob.bc,
    )


def cast_problem(prob: PoissonProblem, dtype: Any) -> PoissonProblem:
    """The same problem with every runtime array cast to ``dtype``.

    The mixed-precision hook: ``make_preconditioner(precond_dtype=...)``
    builds its whole operator/diagonal/transfer chain from the cast copy, so
    every preconditioner byte (HBM streams and, sharded, wire payloads) is
    in the narrow dtype while the outer PCG keeps the original problem.
    Setup metadata (mesh, l2g) is shared, not copied.
    """
    cast = lambda a: None if a is None else a.astype(dtype)
    return dataclasses.replace(
        prob,
        d=prob.d.astype(dtype),
        g=prob.g.astype(dtype),
        jw=prob.jw.astype(dtype),
        w_local=prob.w_local.astype(dtype),
        w_global=prob.w_global.astype(dtype),
        dtype=dtype,
        k=cast(prob.k),
        lam_field=cast(prob.lam_field),
        mask=cast(prob.mask),
    )


def poisson_assembled(
    prob: PoissonProblem,
    local_op: Callable[..., jax.Array] | None = None,
    *,
    fused: bool | None = None,
    fused_kwargs: dict | None = None,
) -> Callable[[jax.Array], jax.Array]:
    """hipBone operator: x_G (N_G,) -> A x_G (N_G,).

    Split form (the default): y_L = (S_L + λW) Z x_G, then the gather
    Z^T y_L.  ``local_op`` lets callers swap in the Pallas element kernel
    for the middle stage; default is the pure-jnp reference.  Z and Z^T
    are the lattice pair (dense slices, pads, adds and a 0/1 product along
    x) when ``prob.l2g`` is the box lattice numbering, else the indexed
    pair (``take`` and ``segment_sum``); ``apply.assembly`` says which
    ("lattice" or "indexed"), and ``obs.tallies()`` counts the operators
    built of each.

    ``fused`` selects the single-kernel form instead
    (``kernels.ops.poisson_assembled_fused``): gather, local operator and
    scatter-add in one Pallas pass, no x_L/y_L HBM round-trips.  It runs
    only through the Pallas interpreter; ``fused=True`` on a native
    backend raises.  ``None`` defers to ``kernels.ops.should_fuse_operator``
    (off unless ``HIPBONE_FUSED=1``) — except when a custom ``local_op``
    is given, which pins the split pipeline that uses it.
    ``fused_kwargs`` passes ``block_e`` / ``interpret`` / ``gather_mode``
    through to the fused wrapper.
    """
    if fused is None:
        if local_op is not None:
            fused = False
        else:
            from ..kernels import ops as _kops  # lazy: kernels import core

            fused = _kops.should_fuse_operator()
    if fused:
        if local_op is not None:
            raise ValueError(
                "poisson_assembled: fused=True replaces the whole "
                "scatter/local_op/gather pipeline; drop local_op"
            )
        from ..kernels import ops as _kops  # lazy: kernels import core

        apply = _kops.make_poisson_assembled_fused(prob, **(fused_kwargs or {}))
    else:
        apply = _split_assembled(prob, local_op or local_poisson)
    obs.tally(f"op.assembly.{apply.assembly}")
    return apply


def _split_assembled(
    prob: PoissonProblem, op: Callable[..., jax.Array]
) -> Callable[[jax.Array], jax.Array]:
    """The split apply Z^T (S_L + λW) Z of :func:`poisson_assembled`."""
    w_eff, lam_eff = screen_stream(prob)
    mask = prob.mask
    asm = assembly(prob.l2g, prob.mesh.n_degree, prob.mesh.shape)

    def apply(x_g: jax.Array) -> jax.Array:
        with obs.scope("op.scatter"):
            if mask is not None:
                x_g = mask * x_g
            x_l = asm.z(x_g)
        with obs.scope("op.local"):
            y_l = op(x_l, prob.g, prob.d, lam_eff, w_eff)
        with obs.scope("op.gather"):
            y_g = asm.zt(y_l)
            return y_g if mask is None else mask * y_g

    apply.fused = False
    apply.assembly = asm.kind
    return apply


def poisson_scattered(
    prob: PoissonProblem,
    local_op: Callable[..., jax.Array] | None = None,
) -> Callable[[jax.Array], jax.Array]:
    """NekBone baseline operator: x_L (E, p) -> b_L = (ZZ^T S_L + λI) x_L.

    The scattered baseline keeps NekBone's algebraic λI screen; variable k
    arrives for free through the folded ``g``, but a λ(x) field or
    Dirichlet mask has no scattered-storage analogue here — the assembled
    path (:func:`poisson_assembled`) is the variable-coefficient surface.
    """
    if prob.lam_field is not None or prob.mask is not None:
        raise NotImplementedError(
            "poisson_scattered is the constant-λ NekBone baseline; "
            "λ(x) fields / Dirichlet masks need the assembled operator "
            "(poisson_assembled)"
        )
    op = local_op or local_poisson

    @obs.scope("op.scattered")
    def apply(x_l: jax.Array) -> jax.Array:
        s_l = op(x_l, prob.g, prob.d, 0.0, None)  # S_L x_L only
        return gather_scatter(s_l, prob.l2g, prob.n_global) + prob.lam * x_l

    return apply
