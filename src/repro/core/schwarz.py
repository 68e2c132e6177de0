"""Overlapping additive-Schwarz smoothing via tensor-product fast diagonalization.

The next rung of the preconditioner ladder after Chebyshev–Jacobi and
p-multigrid: per-element *extended-block* local solves, the smoother that
gives production Nek5000/RS its robustness on deformed / ill-conditioned
meshes (Min et al. 2023).  Each element solves a local screened-Poisson
problem on its own nodes plus ``overlap`` GLL node layers borrowed from
every face neighbor; the solves are exact inverses of a separable
(tensor-product) approximation of the local operator, applied in O(m^4)
per element via the 1-D fast diagonalization of :mod:`core.sem`:

    Â_e = A⊗B⊗B + B⊗A⊗B + B⊗B⊗A + λ·I           (per-direction 1-D A, B)
    Â_e⁻¹ = (T⊗T⊗T) diag(1/(μ_i+μ_j+μ_k+λ s_i s_j s_k)) (T⊗T⊗T)ᵀ

with ``(T_d, μ_d, s_d)`` from ``sem.fast_diagonalization_1d`` of the
extended-interval matrices.  Deformed elements are approximated by an
axis-aligned box with the element's mean directional lengths — the same
approximation Nek makes; the Schwarz apply is a *preconditioner*, so the
approximation error only costs CG iterations, never correctness.

The global apply is symmetric weighted additive Schwarz,

    M⁻¹ = W½ Z_sᵀ blkdiag(Â_e⁻¹) Z_s W½,

where ``Z_s`` is the *extended* scatter (each block also reads its overlap
nodes) and ``W`` the inverse overlap-count weights.  Symmetric weighting
keeps M⁻¹ SPD so plain PCG remains valid; ``weighting="post"`` gives the
classical RAS variant (weights on the output only — slightly stronger per
application but nonsymmetric, for flexible/Richardson use only).

Overlap transport reuses the existing machinery: single-device blocks read
through an extended local-to-global map (a dummy index absorbs
out-of-domain slots); the sharded path (core.distributed) feeds the same
solves from a shell-expanded padded box filled by ``comms.halo``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import sem
from .gather_scatter import gather_masked, scatter_masked


# TPU's default f32 matmul is one bf16 pass; the solver needs full f32
_HI = jax.lax.Precision.HIGHEST

__all__ = [
    "SCHWARZ_INNER_DEGREE",
    "SCHWARZ_WEIGHTINGS",
    "SchwarzFDM",
    "element_lengths",
    "element_neighbor_flags",
    "element_screen_means",
    "build_fdm",
    "fdm_solve",
    "extended_l2g",
    "overlap_counts_1d",
    "overlap_counts_global",
    "make_schwarz_apply",
]

SCHWARZ_WEIGHTINGS = ("sqrt", "post", "none")
# Default Chebyshev degree of the in-eigenbasis block solve.  The algebraic
# screen λI is the one term of the local operator that pure tensor structure
# cannot diagonalize (see build_fdm); degree 7 brings the blocks within a
# few percent of their exact inverses at roughly 4x the bare-FDM apply cost.
SCHWARZ_INNER_DEGREE = 7


@dataclasses.dataclass(frozen=True)
class SchwarzFDM:
    """Per-element fast-diagonalization factors for the extended blocks.

    In the tensor eigenbasis ``T₃⊗T₂⊗T₁`` the local screened operator is

        H = diag(μ_i + μ_j + μ_k) + λ (C₃⊗C₂⊗C₁),   C_d = T_dᵀT_d,

    exactly (``TᵀBT = I`` turns the separable stiffness into the diagonal
    part; the algebraic screen ``λI`` becomes the ``C`` product, which does
    NOT diagonalize — NekBone's screen is the one term that breaks pure
    tensor structure).  The block solve is a fixed-degree Chebyshev
    iteration on ``H`` preconditioned by ``diag(H)⁻¹``, run entirely in the
    eigenbasis: one forward/backward transform pair plus ``inner_degree``
    cheap ``C``-contractions.  ``inner_degree = 1`` is the bare diagonal
    approximation; 2-3 recovers most of the exact-block quality.

    Attributes:
      tmats: (E, 3, m, m) eigenvector matrices, direction order (r, s, t).
      cmats: (E, 3, m, m) Gram matrices ``C_d = T_dᵀT_d``.
      denom_inv: (E, m, m, m) ``1/diag(H)`` in (t, s, r) order.
      musum: (E, m, m, m) tensor eigenvalue sums ``μ_i + μ_j + μ_k``.
      inner_lo / inner_hi: (E,) per-element Chebyshev interval for the
        diagonally-preconditioned ``H`` (setup-time power iteration).
      lam: screen parameter λ — a float (legacy algebraic screen) or an
        (E, 1, 1, 1) per-element array (variable-coefficient blocks),
        broadcasting identically through ``fdm_solve``'s hop.
      overlap: extension width s (m = N + 1 + 2s).
      inner_degree: Chebyshev degree of the block solve.
    """

    tmats: jax.Array
    cmats: jax.Array
    denom_inv: jax.Array
    musum: jax.Array
    inner_lo: jax.Array
    inner_hi: jax.Array
    lam: jax.Array | float
    overlap: int
    inner_degree: int

    @property
    def m(self) -> int:
        return int(self.tmats.shape[-1])


def element_lengths(coords: np.ndarray, n_degree: int) -> np.ndarray:
    """(E, 3) mean physical element lengths along (r, s, t).

    ``coords``: (E, (N+1)^3, 3) node coordinates in (t, s, r) order.  Each
    length is the Euclidean end-to-end distance along one reference
    direction, averaged over the transverse nodes — the axis-aligned-box fit
    of a (possibly deformed) element that the separable FDM operator uses.
    """
    e = coords.shape[0]
    n1 = int(n_degree) + 1
    c3 = coords.reshape(e, n1, n1, n1, 3)  # (E, t, s, r, 3)
    out = np.empty((e, 3))
    for d, axis in enumerate((3, 2, 1)):  # r, s, t
        lo = np.take(c3, 0, axis=axis)
        hi = np.take(c3, n1 - 1, axis=axis)
        out[:, d] = np.linalg.norm(hi - lo, axis=-1).mean(axis=(1, 2))
    return out


def element_neighbor_flags(
    elem_idx: np.ndarray, grid_shape: tuple[int, int, int]
) -> np.ndarray:
    """(E, 3, 2) booleans: does element ``(i, j, k)`` have a (lo, hi)
    neighbor along each direction of the *global* element grid?

    ``elem_idx``: (E, 3) integer element coordinates in the global grid
    (single-device meshes pass 0..shape-1; sharded callers pass rank-offset
    coordinates so rank boundaries correctly count as interior).
    """
    out = np.empty((elem_idx.shape[0], 3, 2), dtype=bool)
    for d in range(3):
        out[:, d, 0] = elem_idx[:, d] > 0
        out[:, d, 1] = elem_idx[:, d] < grid_shape[d] - 1
    return out


def _cprod_apply(
    cr: np.ndarray, cs: np.ndarray, ct: np.ndarray, v: np.ndarray
) -> np.ndarray:
    """(C₃⊗C₂⊗C₁) v on (E, m, m, m) arrays in (t, s, r) order (numpy)."""
    v = np.einsum("eai,etsi->etsa", cr, v)
    v = np.einsum("ebj,etjr->etbr", cs, v)
    v = np.einsum("eck,eksr->ecsr", ct, v)
    return v


def build_fdm(
    lengths: np.ndarray,
    flags: np.ndarray,
    n_degree: int,
    lam,
    overlap: int,
    dtype,
    *,
    inner_degree: int = SCHWARZ_INNER_DEGREE,
    k_elem: np.ndarray | None = None,
    screen: str = "algebraic",
) -> SchwarzFDM:
    """Assemble the per-element FDM factors (numpy setup, cast once).

    Args:
      lengths: (E, 3) directional element lengths (:func:`element_lengths`).
      flags: (E, 3, 2) neighbor-present booleans
        (:func:`element_neighbor_flags`).
      n_degree: polynomial degree N.
      lam: screen parameter λ — a scalar, or an (E,) per-element array
        (element means of a λ(x) field).  The screen keeps every block SPD
        even on an all-Neumann single-element patch where the stiffness
        alone is singular (a tiny floor guards λ = 0).
      overlap: extension width s in GLL nodes (0 = block Jacobi).
      inner_degree: Chebyshev degree of the in-eigenbasis block solve
        (1 = pure diagonal/fast-diagonalization approximation of the
        screen; 2-3 nearly exact).  The per-element Chebyshev interval is
        estimated here by power iteration on the diagonally-preconditioned
        block operator — pure setup-time numpy.
      k_elem: optional (E,) element-mean diffusion coefficients.  Each
        block approximates k_e·(-Δ) by scaling its tensor eigenvalue sums
        — the same axis-aligned-box spirit as the mean-length fit, and
        exact for per-element-constant k (the checker family).
      screen: "algebraic" — the legacy λI screen, which becomes the
        non-diagonal ``λ(C₃⊗C₂⊗C₁)`` in the eigenbasis; "mass" — the weak
        λ·M screen of variable-coefficient problems.  Because the
        eigenbasis is B-orthonormal (``TᵀBT = I``), the mass screen is
        *exactly* λ·I in-basis — implemented by setting the Gram matrices
        C to the identity, which makes the block solve exactly diagonal
        (the one term that breaks tensor structure disappears).

    Returns:
      :class:`SchwarzFDM` with jnp arrays in ``dtype``.
    """
    if screen not in ("algebraic", "mass"):
        raise ValueError(f"unknown fdm screen {screen!r}; 'algebraic'|'mass'")
    e_total = lengths.shape[0]
    n = int(n_degree)
    m = n + 1 + 2 * int(overlap)
    lam_arr = np.asarray(lam, np.float64)
    if lam_arr.ndim == 0:
        lam = float(lam_arr)  # scalar stays a python float (legacy contract)
    elif lam_arr.shape == (e_total,):
        lam = lam_arr[:, None, None, None]  # broadcasts through hop / denom
    else:
        raise ValueError(
            f"lam must be a scalar or ({e_total},) element array, "
            f"got shape {lam_arr.shape}"
        )
    tmats = np.empty((e_total, 3, m, m))
    cmats = np.empty((e_total, 3, m, m))
    mus = np.empty((e_total, 3, m))
    # identical (h, flags) tuples share one eigendecomposition — on regular
    # meshes that is a single factorization for the whole grid
    cache: dict[tuple, tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
    for e in range(e_total):
        for d in range(3):
            key = (round(float(lengths[e, d]), 12), bool(flags[e, d, 0]),
                   bool(flags[e, d, 1]))
            if key not in cache:
                a_ext, b_ext = sem.extended_interval_matrices(
                    n, overlap, key[0], has_lo=key[1], has_hi=key[2]
                )
                cache[key] = sem.fast_diagonalization_1d(a_ext, b_ext)
            t, mu, _ = cache[key]
            tmats[e, d], mus[e, d] = t, mu
            cmats[e, d] = (
                np.eye(m) if screen == "mass" else t.T @ t
            )

    mu_r, mu_s, mu_t = mus[:, 0], mus[:, 1], mus[:, 2]
    musum = (
        mu_t[:, :, None, None] + mu_s[:, None, :, None] + mu_r[:, None, None, :]
    )
    if k_elem is not None:
        musum = musum * np.asarray(k_elem, np.float64)[:, None, None, None]
    s_r, s_s, s_t = (np.einsum("eii->ei", cmats[:, d]) for d in range(3))
    denom = musum + lam * (
        s_t[:, :, None, None] * s_s[:, None, :, None] * s_r[:, None, None, :]
    )
    # λ=0 on an all-Neumann patch leaves the constant mode at exactly 0;
    # floor it so the pseudo-inverse-like apply stays finite
    denom = np.maximum(denom, 1e-12 * denom.max())
    dinv = 1.0 / denom

    # Chebyshev interval of diag(H)⁻¹H per element: its spectrum lies in
    # [1 - r, 1 + r] (H SPD with unit preconditioned diagonal), with r the
    # dominant |eigenvalue| of diag(H)⁻¹H - I from a few power steps.
    rng = np.random.default_rng(12)
    x = rng.standard_normal((e_total, m, m, m))
    r_est = np.ones(e_total)
    cr, cs, ct = cmats[:, 0], cmats[:, 1], cmats[:, 2]
    for _ in range(30):
        y = dinv * (musum * x + lam * _cprod_apply(cr, cs, ct, x)) - x
        nrm = np.sqrt((y * y).sum(axis=(1, 2, 3)))
        r_est = nrm / np.maximum(
            np.sqrt((x * x).sum(axis=(1, 2, 3))), 1e-300
        )
        x = y / np.maximum(nrm, 1e-300)[:, None, None, None]
    hi = 1.0 + 1.05 * r_est
    lo = np.maximum(1.0 - 1.05 * r_est, 0.05 * hi)
    # λ=0 (or any exactly-diagonal H) collapses the interval to a point;
    # widen it symmetrically so the Chebyshev recurrence stays finite while
    # the interval midpoint — which alone enters the degree-1 stage — is
    # untouched (the solve is exact after that first stage in this case)
    mid, half = 0.5 * (hi + lo), 0.5 * (hi - lo)
    half = np.maximum(half, 1e-3 * mid)
    lo, hi = mid - half, mid + half

    return SchwarzFDM(
        tmats=jnp.asarray(tmats, dtype),
        cmats=jnp.asarray(cmats, dtype),
        denom_inv=jnp.asarray(dinv, dtype),
        musum=jnp.asarray(musum, dtype),
        inner_lo=jnp.asarray(lo[:, None, None, None], dtype),
        inner_hi=jnp.asarray(hi[:, None, None, None], dtype),
        lam=lam if isinstance(lam, float) else jnp.asarray(lam, dtype),
        overlap=int(overlap),
        inner_degree=int(inner_degree),
    )


def fdm_solve(fdm: SchwarzFDM, u: jax.Array) -> jax.Array:
    """Batched extended-block solves ``Â_e⁻¹ u_e`` via tensor contractions.

    ``u``: (E, m^3) extended-block right-hand sides in (t, s, r) node order.
    One transform pair into/out of the tensor eigenbasis (three batched
    contractions each — the operator's MXU pattern with per-element
    matrices) around a degree-``inner_degree`` diagonally-preconditioned
    Chebyshev solve of the in-basis block operator ``H``.  The iteration
    is a fixed polynomial ``q(D⁻¹H) D⁻¹`` per element, hence a symmetric
    linear map — the Schwarz apply stays PCG-valid.
    """
    from .precond import chebyshev_apply  # local import (precond imports us)

    e = u.shape[0]
    m = fdm.m
    tr, ts, tt = fdm.tmats[:, 0], fdm.tmats[:, 1], fdm.tmats[:, 2]
    cr, cs, ct = fdm.cmats[:, 0], fdm.cmats[:, 1], fdm.cmats[:, 2]
    u3 = u.reshape(e, m, m, m)
    # into the eigenbasis: Tᵀ along each direction
    u3 = jnp.einsum("eai,etsa->etsi", tr, u3, precision=_HI)
    u3 = jnp.einsum("ebj,etbr->etjr", ts, u3, precision=_HI)
    u3 = jnp.einsum("eck,ecsr->eksr", tt, u3, precision=_HI)

    def hop(v: jax.Array) -> jax.Array:
        cv = jnp.einsum("eai,etsi->etsa", cr, v, precision=_HI)
        cv = jnp.einsum("ebj,etjr->etbr", cs, cv, precision=_HI)
        cv = jnp.einsum("eck,eksr->ecsr", ct, cv, precision=_HI)
        return fdm.musum * v + fdm.lam * cv

    # the (E,1,1,1) per-element intervals broadcast through the shared
    # semi-iteration: E independent Chebyshev solves in one trace
    solve = chebyshev_apply(
        hop,
        lambda v: fdm.denom_inv * v,
        fdm.inner_hi,
        lmin=fdm.inner_lo,
        degree=fdm.inner_degree,
    )
    z = solve(u3)

    # back out: T along each direction
    z = jnp.einsum("eai,etsi->etsa", tr, z, precision=_HI)
    z = jnp.einsum("ebj,etjr->etbr", ts, z, precision=_HI)
    z = jnp.einsum("eck,eksr->ecsr", tt, z, precision=_HI)
    return z.reshape(e, -1)


def extended_l2g(
    n_degree: int, shape: tuple[int, int, int], overlap: int
) -> np.ndarray:
    """Extended local-to-global map Z_s for a single-device box mesh.

    (E, m^3) int32 with m = N+1+2s; entry = global DOF of each extended
    block node, or the dummy index ``n_global`` for out-of-domain slots
    (callers scatter from a zero-padded vector and drop the dummy segment
    on the gather).  Element and node orderings match ``mesh.build_box_mesh``.
    """
    ex, ey, ez = shape
    n = int(n_degree)
    s = int(overlap)
    gx, gy, gz = ex * n + 1, ey * n + 1, ez * n + 1
    n_global = gx * gy * gz

    a = np.arange(-s, n + s + 1)
    la, lb, lc = np.meshgrid(a, a, a, indexing="ij")  # (r, s, t)
    loc_a = la.transpose(2, 1, 0).reshape(-1)
    loc_b = lb.transpose(2, 1, 0).reshape(-1)
    loc_c = lc.transpose(2, 1, 0).reshape(-1)

    ei, ej, ek = np.meshgrid(
        np.arange(ex), np.arange(ey), np.arange(ez), indexing="ij"
    )
    ei = ei.transpose(2, 1, 0).reshape(-1)
    ej = ej.transpose(2, 1, 0).reshape(-1)
    ek = ek.transpose(2, 1, 0).reshape(-1)

    gxi = ei[:, None] * n + loc_a[None, :]
    gyj = ej[:, None] * n + loc_b[None, :]
    gzk = ek[:, None] * n + loc_c[None, :]
    valid = (
        (gxi >= 0) & (gxi < gx)
        & (gyj >= 0) & (gyj < gy)
        & (gzk >= 0) & (gzk < gz)
    )
    l2g = gxi + gx * (gyj + gy * gzk)
    return np.where(valid, l2g, n_global).astype(np.int32)


def overlap_counts_1d(ne: int, n_degree: int, overlap: int) -> np.ndarray:
    """Per-grid-line count of extended element windows along one axis.

    ``counts[q] = #{elements i : i·N - s <= q <= i·N + N + s}`` for the
    global 1-D grid coordinate q — the separable factor of the Schwarz
    overlap multiplicity (the 3-D count is the product over axes), used to
    build the partition-of-unity weights identically on the single-device
    and sharded paths.
    """
    n, s = int(n_degree), int(overlap)
    q = np.arange(ne * n + 1)
    i = np.arange(ne)
    inside = (q[:, None] >= i[None, :] * n - s) & (
        q[:, None] <= i[None, :] * n + n + s
    )
    return inside.sum(axis=1).astype(np.float64)


def overlap_counts_global(
    n_degree: int, shape: tuple[int, int, int], overlap: int
) -> np.ndarray:
    """(N_G,) overlap multiplicity of every assembled DOF (x fastest)."""
    cx = overlap_counts_1d(shape[0], n_degree, overlap)
    cy = overlap_counts_1d(shape[1], n_degree, overlap)
    cz = overlap_counts_1d(shape[2], n_degree, overlap)
    return (
        cz[:, None, None] * cy[None, :, None] * cx[None, None, :]
    ).reshape(-1)


def element_screen_means(prob) -> tuple[np.ndarray | None, object, str]:
    """``(k_elem, lam, screen)`` for :func:`build_fdm` from a problem.

    Element means of the coefficient fields: the Schwarz blocks are already
    an axis-aligned separable *approximation* of each element, so per-block
    mean coefficients are the natural (and for per-element-constant
    families, exact) extension — any residual variation is absorbed by the
    outer Chebyshev/CG like the geometry approximation is.  Legacy
    problems return ``(None, λ, "algebraic")`` — bit-identical factors.
    """
    k_e = (
        None if prob.k is None
        else np.asarray(prob.k, np.float64).mean(axis=1)
    )
    if prob.lam_field is None:
        return k_e, float(prob.lam), "algebraic"
    return k_e, np.asarray(prob.lam_field, np.float64).mean(axis=1), "mass"


def make_schwarz_apply(
    prob,
    *,
    overlap: int = 1,
    weighting: str = "sqrt",
    inner_degree: int = SCHWARZ_INNER_DEGREE,
) -> Callable[[jax.Array], jax.Array]:
    """Single-device overlapping-Schwarz application z = M⁻¹ r.

    Args:
      prob: a ``PoissonProblem`` (assembled storage).
      overlap: extension width s in GLL nodes; 0 degenerates to FDM
        block Jacobi (the blocks still overlap at shared element faces).
      weighting: "sqrt" (default) — symmetric weighted additive Schwarz
        ``W½ Z_sᵀ Â⁻¹ Z_s W½``, SPD, valid for plain PCG; "post" —
        RAS-style output-side weighting ``W Z_sᵀ Â⁻¹ Z_s`` (nonsymmetric);
        "none" — unweighted additive Schwarz (symmetric, overcounts
        overlap regions).
      inner_degree: Chebyshev degree of the in-eigenbasis block solve
        (see :func:`build_fdm`).

    Returns:
      ``apply(r) -> z`` on assembled (N_G,) vectors.
    """
    if weighting not in SCHWARZ_WEIGHTINGS:
        raise ValueError(
            f"unknown weighting {weighting!r}; choose from {SCHWARZ_WEIGHTINGS}"
        )
    mesh = prob.mesh
    k_elem, lam_fdm, screen = element_screen_means(prob)
    fdm = build_fdm(
        element_lengths(mesh.coords, mesh.n_degree),
        element_neighbor_flags(_element_indices(mesh.shape), mesh.shape),
        mesh.n_degree,
        lam_fdm,
        overlap,
        prob.dtype,
        inner_degree=inner_degree,
        k_elem=k_elem,
        screen=screen,
    )
    l2g_ext = jnp.asarray(extended_l2g(mesh.n_degree, mesh.shape, overlap))
    counts = overlap_counts_global(mesh.n_degree, mesh.shape, overlap)
    if weighting == "sqrt":
        w_in = w_out = jnp.asarray(1.0 / np.sqrt(counts), prob.dtype)
    elif weighting == "post":
        w_in, w_out = None, jnp.asarray(1.0 / counts, prob.dtype)
    else:
        w_in = w_out = None
    n_global = prob.n_global
    bc_mask = prob.mask

    def apply(r: jax.Array) -> jax.Array:
        # mask ∘ M ∘ mask keeps the Schwarz apply SPD on the Dirichlet-
        # interior subspace (the extended blocks read across the boundary)
        rw = r if bc_mask is None else bc_mask * r
        rw = rw if w_in is None else w_in * rw
        z = fdm_solve(fdm, scatter_masked(rw, l2g_ext))
        out = gather_masked(z, l2g_ext, n_global)
        out = out if w_out is None else w_out * out
        return out if bc_mask is None else bc_mask * out

    return apply


def _element_indices(shape: tuple[int, int, int]) -> np.ndarray:
    """(E, 3) element grid coordinates in build_box_mesh flat order."""
    ex, ey, ez = shape
    ei, ej, ek = np.meshgrid(
        np.arange(ex), np.arange(ey), np.arange(ez), indexing="ij"
    )
    return np.stack(
        [
            ei.transpose(2, 1, 0).reshape(-1),
            ej.transpose(2, 1, 0).reshape(-1),
            ek.transpose(2, 1, 0).reshape(-1),
        ],
        axis=1,
    )
