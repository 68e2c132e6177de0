"""Preconditioners for the screened-Poisson CG solve.

NekBone (and hence hipBone) fixes 100 unpreconditioned CG iterations, but
the parent applications do not: production Nek5000/RS Poisson solves are
preconditioned (Jacobi, Chebyshev-accelerated Jacobi, Schwarz, p-multigrid).
This module supplies the first three rungs of that ladder on top of the
existing assembled-storage machinery:

  * **Jacobi**: ``M = diag(A)`` where ``A = Z^T (S_L + λW) Z``.  The
    assembled diagonal is computed *without materializing S* — the
    element-local diagonal of the tensor-product stiffness

        diag(S_L^e)[t,s,r] = Σ_i D[i,r]² G_rr[t,s,i]
                           + Σ_j D[j,s]² G_ss[t,j,r]
                           + Σ_k D[k,t]² G_tt[k,s,r]
                           + 2 (D_rr D_ss G_rs + D_rr D_tt G_rt
                                + D_ss D_tt G_st)[t,s,r]

    (the three contractions are the divergence einsums with D squared and
    the diagonal metric blocks; the cross terms collapse to products of
    the diagonal entries of D), then gathered with Z^T like any other
    element-local field.

  * **Chebyshev–Jacobi**: a degree-k Chebyshev polynomial in the
    Jacobi-preconditioned operator ``D⁻¹A``, i.e. ``M⁻¹ = q_k(D⁻¹A) D⁻¹``.
    Because q_k is a fixed polynomial the map r → z is *linear and
    symmetric* (D^{1/2}-similarity), so plain PCG remains valid — no
    flexible-CG machinery needed.  The spectrum bound λ_max(D⁻¹A) is
    estimated by power iteration from a deterministic high-frequency seed
    vector; the smoothing interval is the usual [λ_max/ratio, safety·λ_max].

  * **p-multigrid** (``pmg``): the production Nek5000/RS configuration — a
    V-cycle over a degree ladder N → ⌈N/2⌉ → … → 1 with Chebyshev–Jacobi
    smoothing on every level and a direct (or Chebyshev/Jacobi-iterated)
    solve on the degree-1 coarsest level.  Transfers are the tensor-product
    lift of the 1-D GLL interpolation matrix (``sem.interpolation_matrix``);
    prolongation is nodal interpolation expressed through the assembled
    machinery as ``P = Z_f^T W_f Ĵ Z_c`` (averaging gather of the
    element-local interpolant) and restriction is its *exact transpose*
    ``R = Z_c^T Ĵ^T W_f Z_f``, so the V-cycle is a symmetric linear map and
    plain PCG remains valid.

  * **overlapping Schwarz** (``schwarz``): per-element extended-block local
    solves via tensor-product fast diagonalization (core.schwarz), combined
    as symmetric weighted additive Schwarz — the Nek5000/RS smoother for
    deformed / ill-conditioned meshes.  Available standalone
    (``make_preconditioner("schwarz", ...)``) and as the pMG smoother
    (``make_pmg_preconditioner(smoother="schwarz")``, Chebyshev-accelerated
    the way nekRS runs it).

Everything here is expressed through the caller's ``operator`` /
``dot`` / ``psum`` callables, so the same code serves the single-device
assembled path and the sharded padded-box path in core.distributed (where
dots are replica-masked and psum is a real collective).

**Precision is a first-class axis**: ``make_preconditioner(...,
precond_dtype=jnp.float32)`` builds the whole ladder rung — diagonals,
Chebyshev A-apply chains, Schwarz FDM blocks, every pMG level and transfer
— in fp32 and wraps it in a single :func:`cast_apply` boundary, so an fp64
outer PCG streams half the preconditioner bytes (the production
Nek5000/NekRS trick).  The fp32 apply is symmetric only to fp32 roundoff
when viewed from fp64, so pair it with ``cg_variant="flexible"``
(core.cg) near tight tolerances.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import sem
from .gather_scatter import Assembly, assembly, indexed_assembly
from .schwarz import SCHWARZ_INNER_DEGREE, make_schwarz_apply


# TPU's default f32 matmul is one bf16 pass; the solver needs full f32
_HI = jax.lax.Precision.HIGHEST

__all__ = [
    "local_operator_diagonal",
    "level_assembly",
    "assembled_diagonal",
    "masked_dinv",
    "masked_seed",
    "power_lambda_max",
    "lanczos_extremes",
    "jacobi_apply",
    "chebyshev_apply",
    "cast_apply",
    "deterministic_seed_vector",
    "tensor3_interp",
    "pmg_degree_ladder",
    "make_transfer_pair",
    "make_vcycle",
    "make_pmg_preconditioner",
    "make_preconditioner",
    "precond_signature",
    "PRECOND_KINDS",
    "PMG_SMOOTHERS",
    "PMG_COARSE_OPS",
    "CHEB_LMIN_RATIO",
    "CHEB_SAFETY",
    "CHEB_LMIN_SAFETY",
    "PMG_SMOOTH_RATIO",
    "SCHWARZ_INNER_DEGREE",
    "pmg_smooth_degree_default",
    "smoother_interval",
]

PRECOND_KINDS = ("none", "jacobi", "chebyshev", "schwarz", "pmg")
PMG_SMOOTHERS = ("chebyshev", "schwarz")
PMG_COARSE_OPS = ("redisc", "galerkin", "galerkin_mat")

# Standard Chebyshev-smoother interval: [lmax/ratio, safety * lmax].
CHEB_LMIN_RATIO = 30.0
CHEB_SAFETY = 1.1
# Lanczos interior Ritz values overestimate λ_min — back the bound off.
CHEB_LMIN_SAFETY = 0.8
# pMG smoother targets the top 1/ratio of the spectrum; the rest is the
# coarse grid's job (degree halving shifts roughly half the spectrum down).
# When Lanczos says the whole spectrum sits above lmax/ratio (well-conditioned
# large-λ regime) the interval tightens to [0.8·λ_min, 1.1·λ_max] instead.
PMG_SMOOTH_RATIO = 6.0
PMG_SMOOTH_DEGREE = 4
# Schwarz-smoothed V-cycles need fewer Chebyshev stages per sweep — each
# Schwarz application is already a strong (near-block-exact) smoother.
PMG_SCHWARZ_SMOOTH_DEGREE = 2


def local_operator_diagonal(
    g: jax.Array,
    d: jax.Array,
    lam: jax.Array | float,
    w: jax.Array | None,
) -> jax.Array:
    """Element-local diagonal of (S_L + λ·screen) without forming S_L.

    Args:
      g: (E, 6, p) packed geometric factors [rr, rs, rt, ss, st, tt].
      d: (N+1, N+1) 1-D derivative matrix.
      lam: screen parameter λ.
      w: (E, p) inverse-degree weights (hipBone λW screen) or None (λI).

    Returns:
      (E, p) local diagonal, node order (t, s, r) matching local_poisson.
    """
    e = g.shape[0]
    n1 = d.shape[0]
    d2 = d * d
    g3 = g.reshape(e, 6, n1, n1, n1)

    # Same contraction patterns as the divergence in local_poisson, with D²
    # and the diagonal metric blocks.
    diag = (
        jnp.einsum("ia,etsi->etsa", d2, g3[:, 0], precision=_HI)   # Σ_i D[i,r]² G_rr
        + jnp.einsum("jb,etjr->etbr", d2, g3[:, 3], precision=_HI)  # Σ_j D[j,s]² G_ss
        + jnp.einsum("kc,eksr->ecsr", d2, g3[:, 5], precision=_HI)  # Σ_k D[k,t]² G_tt
    )
    dd = jnp.diagonal(d)
    ddr = dd.reshape(1, 1, 1, n1)
    dds = dd.reshape(1, 1, n1, 1)
    ddt = dd.reshape(1, n1, 1, 1)
    diag = diag + 2.0 * (
        ddr * dds * g3[:, 1] + ddr * ddt * g3[:, 2] + dds * ddt * g3[:, 4]
    )
    diag = diag.reshape(e, -1)

    screen = jnp.ones_like(diag) if w is None else w
    return diag + lam * screen


def level_assembly(
    l2g, n_degree: int, box_shape: tuple[int, int, int], blocks=None
) -> Assembly:
    """Z and Zᵀ of one level for its smoother diagonal and pMG transfers.

    The arguments are :func:`gather_scatter.assembly`'s, on one device (the
    mesh's element box) and on a rank (its padded box and all its element
    blocks) alike.  Both paths take the indexed form here on every map;
    returning ``assembly(l2g, n_degree, box_shape, blocks)`` instead moves
    both to the lattice form where the map allows it.
    """
    return indexed_assembly(l2g, int(np.prod([e * n_degree + 1 for e in box_shape])))


def _own_assembly(prob) -> Assembly:
    """:func:`level_assembly` of a single-device problem."""
    return level_assembly(prob.l2g, prob.n_degree, prob.mesh.shape)


def assembled_diagonal(prob, asm: Assembly | None = None) -> jax.Array:
    """diag(A) on assembled DOFs: Z^T diag(S_L + λ·screen) Z (Z picks out
    the diagonal entries, so this is just the gather of the local diagonal).

    ``asm`` is the level's Z/Zᵀ (default: :func:`level_assembly` of a
    single-device problem).  The screen factors come from
    ``operator.screen_stream`` — the algebraic
    λW pair on legacy problems, the mass-weighted JW·λ(x) stream on
    variable-coefficient ones (k(x) is already folded into ``prob.g``).
    ``prob`` may also be one rank's view of a sharded level (its ``g``,
    ``w_local`` and ``screen`` that rank's shards): with that rank's ``asm``
    the result is its padded box's local sum, which one sum exchange
    completes.
    Deliberately *unmasked* even when ``prob.mask`` is set: the diagonal of
    the unmasked operator is strictly positive everywhere, so ``1/diag``
    stays finite; consumers keep M⁻¹ in the Dirichlet-interior subspace by
    multiplying the *inverse* by the mask (see :func:`masked_dinv`).
    """
    from .operator import screen_stream  # lazy: mirrors sibling call sites

    w_eff, lam_eff = screen_stream(prob)
    dloc = local_operator_diagonal(prob.g, prob.d, lam_eff, w_eff)
    return (asm or _own_assembly(prob)).zt(dloc)


def masked_dinv(prob, diag: jax.Array) -> jax.Array:
    """Inverse diagonal restricted to the Dirichlet-interior subspace.

    ``mask ∘ D⁻¹`` (elementwise, hence = mask∘D⁻¹∘mask): zero on Dirichlet
    DOFs, so every Jacobi/Chebyshev base built from it maps into — and
    Lanczos/power iterates stay inside — the subspace where the masked
    operator is SPD.  No-op on unmasked (legacy) problems.
    """
    dinv = 1.0 / diag
    return dinv if prob.mask is None else prob.mask * dinv


def masked_seed(prob, v0: jax.Array) -> jax.Array:
    """Spectrum-estimation seed projected into the BC subspace.

    Unmasked seed components on Dirichlet DOFs sit in the null space of
    the masked operator: they never propagate under A but linger in the
    Lanczos orthogonalization, dragging the λ_min Ritz value toward 0 and
    wrecking the Chebyshev interval.  No-op on legacy problems.
    """
    return v0 if prob.mask is None else prob.mask * v0


def _default_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.vdot(a, b, precision=_HI)


def _base_apply(
    dinv: jax.Array | Callable[[jax.Array], jax.Array],
) -> Callable[[jax.Array], jax.Array]:
    """Normalize a base preconditioner: a diagonal array or a callable."""
    return dinv if callable(dinv) else (lambda r: dinv * r)


def power_lambda_max(
    operator: Callable[[jax.Array], jax.Array],
    dinv: jax.Array | Callable[[jax.Array], jax.Array],
    v0: jax.Array,
    *,
    iters: int = 15,
    dot: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
    psum: Callable[[jax.Array], jax.Array] | None = None,
) -> jax.Array:
    """λ_max(M⁻¹A) by power iteration from ``v0``.

    ``dinv`` is the base preconditioner M⁻¹: the inverse assembled diagonal
    (array, the Jacobi case) or any SPD apply callable (e.g. the Schwarz
    application).  M⁻¹A is similar to the SPD matrix M^{-1/2} A M^{-1/2},
    so the dominant eigenvalue is real and positive and plain power
    iteration converges.  ``dot``/``psum`` let the distributed caller mask
    replicas and reduce across ranks; the growth ratio ‖w‖/‖v‖ is the
    eigenvalue estimate.

    Returns:
      Scalar λ_max estimate (traced; a raw Ritz value — callers apply
      their own safety factors).
    """
    dp = dot or _default_dot
    allsum = psum or (lambda v: v)
    base = _base_apply(dinv)

    def body(carry, _):
        v, _ = carry
        w = base(operator(v))
        nrm = jnp.sqrt(allsum(dp(w, w)))
        lam = nrm / jnp.sqrt(allsum(dp(v, v)))
        return (w / jnp.maximum(nrm, 1e-30), lam), lam

    v0 = v0 / jnp.sqrt(allsum(dp(v0, v0)))
    (_, lam), _ = jax.lax.scan(body, (v0, jnp.array(0.0, v0.dtype)), None, length=iters)
    return lam


def deterministic_seed_vector(n: int, dtype=None) -> jax.Array:
    """Reproducible high-frequency start vector for the power iteration.

    A smooth vector (ones) is nearly the *lowest* mode of D⁻¹A; this hash
    puts energy in the top of the spectrum so few iterations suffice.  The
    same formula evaluated on *global* indices is what the distributed path
    uses, keeping replicas consistent by construction.

    ``dtype=None`` resolves to the canonical float dtype (fp64 under
    jax_enable_x64) — every solver call site passes the problem dtype
    explicitly so the seed follows the solve precision; the hash itself is
    always evaluated in numpy fp64 and *then* cast, so the fp32 seed is
    exactly the rounded fp64 seed (dtype-stable determinism).
    """
    if dtype is None:
        dtype = jnp.asarray(0.0).dtype
    return jnp.asarray(seed_values(np.arange(n)), dtype)


def seed_values(global_idx: np.ndarray) -> np.ndarray:
    """sin-hash of global DOF indices (numpy, evaluated at setup time)."""
    t = np.sin((global_idx.astype(np.float64) + 1.0) * 12.9898) * 43758.5453
    return t - np.floor(t) - 0.5


def lanczos_extremes(
    operator: Callable[[jax.Array], jax.Array],
    dinv: jax.Array,
    v0: jax.Array,
    *,
    iters: int = 10,
    dot: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
    psum: Callable[[jax.Array], jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(λ_min, λ_max) estimates of D⁻¹A by a few Lanczos steps.

    Lanczos runs on the symmetrized operator B = D^{-1/2} A D^{-1/2}
    (similar to D⁻¹A, so same spectrum); the extremal eigenvalues of the
    k×k tridiagonal are the Ritz estimates.  Unlike power iteration this
    yields *both* ends of the spectrum, so the Chebyshev interval can be
    tight in the well-conditioned (large-λ) regime instead of the fixed
    λ_max/30 lower bound.  Ritz values approach extremes from inside, so
    callers should widen by CHEB_SAFETY / CHEB_LMIN_SAFETY.

    ``dot``/``psum`` as in :func:`power_lambda_max`; the loop is a static
    python unroll (iters is small), traceable inside shard_map.  Unlike
    :func:`power_lambda_max` this needs the *diagonal* ``dinv`` (the
    symmetrization splits D^{-1/2} to both sides); callable base
    preconditioners use power iteration instead.

    Returns:
      ``(λ_min, λ_max)`` Ritz estimates (traced scalars, no safety factors).
    """
    if callable(dinv):
        raise TypeError(
            "lanczos_extremes needs the diagonal dinv array (it splits "
            "D^-1/2 symmetrically); use power_lambda_max for callable bases"
        )
    dp = dot or _default_dot
    allsum = psum or (lambda v: v)
    k = max(2, min(int(iters), int(np.prod(v0.shape)) - 1))
    dhalf = jnp.sqrt(dinv)
    bop = lambda v: dhalf * operator(dhalf * v)

    v = v0 / jnp.sqrt(allsum(dp(v0, v0)))
    v_prev = jnp.zeros_like(v0)
    beta = jnp.array(0.0, v0.dtype)
    alive = jnp.array(1.0, v0.dtype)   # zeroed after an invariant-subspace breakdown
    alphas, betas = [], []
    for _ in range(k):
        w = bop(v)
        alpha = allsum(dp(v, w))
        w = w - alpha * v - beta * v_prev
        beta_new = jnp.sqrt(jnp.maximum(allsum(dp(w, w)), 0.0))
        # after a breakdown v is zero, so alpha is a spurious 0 that would
        # pollute the Ritz extremes; substitute the first Rayleigh quotient,
        # an interior point of the true spectrum (step 0 is always alive)
        alphas.append(alpha if not alphas else jnp.where(alive > 0, alpha, alphas[0]))
        betas.append(beta_new * alive)
        v_prev = v
        # on breakdown (beta ~ 0) freeze: the Krylov space is invariant and
        # later steps would amplify roundoff into spurious Ritz values
        alive = alive * (beta_new > 1e-12 * jnp.abs(alpha)).astype(alive.dtype)
        v = alive * w / jnp.maximum(beta_new, 1e-30)
        beta = beta_new * alive
    tmat = (
        jnp.diag(jnp.stack(alphas))
        + jnp.diag(jnp.stack(betas[:-1]), 1)
        + jnp.diag(jnp.stack(betas[:-1]), -1)
    )
    eig = jnp.linalg.eigvalsh(tmat)
    lmax = eig[-1]
    # safety net only (post-breakdown eigenvalues are already interior):
    # keep the interval inside (0, lmax] whatever the estimates did
    lmin = jnp.clip(eig[0], lmax * 1e-4, lmax / 1.2)
    return lmin, lmax


def jacobi_apply(dinv: jax.Array) -> Callable[[jax.Array], jax.Array]:
    """z = D⁻¹ r."""
    return lambda r: dinv * r


def chebyshev_apply(
    operator: Callable[[jax.Array], jax.Array],
    dinv: jax.Array | Callable[[jax.Array], jax.Array],
    lmax: jax.Array | float,
    *,
    lmin: jax.Array | float | None = None,
    degree: int = 2,
    fused_d_update: Callable[..., jax.Array] | None = None,
) -> Callable[[jax.Array], jax.Array]:
    """Degree-k Chebyshev-accelerated preconditioner application z ≈ A⁻¹ r.

    The classic Chebyshev semi-iteration for A z = r with z₀ = 0 on the
    interval [lmin, lmax] of M⁻¹A, where the base preconditioner M⁻¹ is
    ``dinv`` — the inverse assembled diagonal (array, the Chebyshev–Jacobi
    case) or any SPD apply callable (Chebyshev-accelerated Schwarz, the
    nekRS smoother configuration).  Each step costs one A-apply and one
    M⁻¹-apply.  Under sharding the A-applies reuse the communication-hiding
    split operator, so Chebyshev needs *no new exchange machinery*.

    The result is a fixed polynomial ``q(M⁻¹A) M⁻¹`` — a symmetric linear
    map whenever M⁻¹ is symmetric (M^{1/2}-similarity), so plain PCG stays
    valid with any base preconditioner from this module.

    ``fused_d_update`` optionally fuses the streaming update
    d ← a·d + c·(M⁻¹ res) (signature (a, c, d, r) -> d_new; see
    kernels.ops.fused_cheb_d_update).

    Returns:
      ``apply(r) -> z``, same vector layout as ``operator``.
    """
    if degree < 1:
        raise ValueError(f"chebyshev degree must be >= 1, got {degree}")
    lmax = jnp.asarray(lmax)
    lmin_v = lmax / CHEB_LMIN_RATIO if lmin is None else jnp.asarray(lmin)
    theta = 0.5 * (lmax + lmin_v)
    delta = 0.5 * (lmax - lmin_v)
    sigma = theta / delta

    base = _base_apply(dinv)
    dupd = fused_d_update or (lambda a, c, d, r: a * d + c * r)

    def apply(r: jax.Array) -> jax.Array:
        rho = 1.0 / sigma
        d = base(r) / theta
        z = d
        res = r
        # degree is a small static int: unrolled at trace time, one compiled
        # A-apply chain per CG iteration body.
        for _ in range(degree - 1):
            res = res - operator(d)
            rho_new = 1.0 / (2.0 * sigma - rho)
            d = dupd(rho_new * rho, 2.0 * rho_new / delta, d, base(res))
            z = z + d
            rho = rho_new
        return z

    return apply


def chebyshev_apply_deferred(
    operator: Callable[[jax.Array], jax.Array],
    operator_pair: Callable[[jax.Array, jax.Array], jax.Array],
    dinv: jax.Array,
    lmax: jax.Array | float,
    *,
    lmin: jax.Array | float | None = None,
    degree: int = 2,
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Chebyshev–Jacobi apply whose FIRST A-apply consumes a deferred input.

    The cross-level V-cycle overlap hands each coarse level its residual as
    a ``(raw, consistent)`` pair: ``raw`` is the restriction *before* its
    halo sum-exchange, bitwise final on every interior slot (the exchange
    only rewrites face slabs), while ``consistent`` carries the exchange.
    Because the Jacobi base is elementwise, ``d = D⁻¹ raw / θ`` matches
    ``D⁻¹ con / θ`` bitwise on the interior — so the first A-apply's
    *interior* element block can start from ``raw`` with no data dependence
    on the restriction exchange, and XLA overlaps that exchange with the
    finer level's interior work.  ``operator_pair(d_raw, d_con)`` is that
    split A-apply (interior gathers from the first argument); it must equal
    ``operator(d_con)`` bitwise, which keeps this whole apply bit-identical
    to :func:`chebyshev_apply` on the consistent input.

    Only valid for an *array* ``dinv`` base (elementwise); Schwarz bases
    transport face values through their expand shells and cannot defer.

    Returns:
      ``apply(raw, con) -> z`` equal bitwise to
      ``chebyshev_apply(...)(con)``.
    """
    if degree < 1:
        raise ValueError(f"chebyshev degree must be >= 1, got {degree}")
    lmax = jnp.asarray(lmax)
    lmin_v = lmax / CHEB_LMIN_RATIO if lmin is None else jnp.asarray(lmin)
    theta = 0.5 * (lmax + lmin_v)
    delta = 0.5 * (lmax - lmin_v)
    sigma = theta / delta

    def apply(raw: jax.Array, con: jax.Array) -> jax.Array:
        rho = 1.0 / sigma
        d = dinv * con / theta
        z = d
        res = con
        for step in range(degree - 1):
            if step == 0 and raw is not con:
                d_raw = dinv * raw / theta
                res = res - operator_pair(d_raw, d)
            else:
                res = res - operator(d)
            rho_new = 1.0 / (2.0 * sigma - rho)
            d = rho_new * rho * d + (2.0 * rho_new / delta) * (dinv * res)
            z = z + d
            rho = rho_new
        return z

    return apply


# ---------------------------------------------------------------------------
# p-multigrid: degree ladder, transfers, V-cycle
# ---------------------------------------------------------------------------


def pmg_smooth_degree_default(smoother: str) -> int:
    """Default Chebyshev stages per pMG smoothing sweep for a base kind.

    Schwarz applications are already strong (near-block-exact) smoothers,
    so they take fewer acceleration stages than pointwise Jacobi.
    """
    return (
        PMG_SCHWARZ_SMOOTH_DEGREE if smoother == "schwarz"
        else PMG_SMOOTH_DEGREE
    )


def smoother_interval(
    operator: Callable[[jax.Array], jax.Array],
    base: jax.Array | Callable[[jax.Array], jax.Array],
    v0: jax.Array,
    *,
    smoother: str,
    lanczos_iters: int = 10,
    dot: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
    psum: Callable[[jax.Array], jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-level pMG smoothing interval — one policy for every solver path.

    The Chebyshev base ("chebyshev", diagonal ``base``) takes both interval
    ends from Lanczos, tightened to
    [max(0.8·λ_min, λ_max/PMG_SMOOTH_RATIO), λ_max]; the Schwarz base
    (callable ``base``) uses power iteration for λ_max(M⁻¹A) and the fixed
    λ_max/PMG_SMOOTH_RATIO bottom (the Schwarz-preconditioned spectrum is
    already compressed).  ``lanczos_iters`` budgets the estimation on both
    branches; the power branch runs 1.5x the steps, since power iteration
    approaches λ_max markedly slower than a Lanczos Ritz value (at the
    default 10 that recovers the 15-step power budget the standalone
    estimators use).  Callers multiply λ_max by CHEB_SAFETY themselves.

    Returns:
      ``(lo, lmax, lmin)`` traced scalars — the interval bottom, the raw
      λ_max Ritz estimate, and the raw λ_min estimate (λ_max/ratio for the
      Schwarz base, where no lower Ritz value exists).
    """
    if smoother == "schwarz":
        lmax_e = power_lambda_max(
            operator, base, v0,
            iters=max(2, (3 * lanczos_iters) // 2),
            dot=dot, psum=psum,
        )
        lo = lmax_e / PMG_SMOOTH_RATIO
        return lo, lmax_e, lo
    lmin_e, lmax_e = lanczos_extremes(
        operator, base, v0, iters=lanczos_iters, dot=dot, psum=psum
    )
    lo = jnp.maximum(CHEB_LMIN_SAFETY * lmin_e, lmax_e / PMG_SMOOTH_RATIO)
    return lo, lmax_e, lmin_e


def pmg_degree_ladder(n: int) -> tuple[int, ...]:
    """The p-MG degree hierarchy N → ⌈N/2⌉ → … → 1 (Nek5000/RS halving)."""
    n = int(n)
    if n < 2:
        raise ValueError(f"p-multigrid needs fine degree >= 2, got N={n}")
    ladder = [n]
    while ladder[-1] > 1:
        ladder.append((ladder[-1] + 1) // 2)
    return tuple(ladder)


def tensor3_interp(j: jax.Array, u: jax.Array) -> jax.Array:
    """Tensor-product lift (J ⊗ J ⊗ J) u on element-local fields.

    ``u``: (E, (n_in+1)^3) in (t, s, r) node order; ``j``: (n_out+1, n_in+1)
    1-D interpolation matrix.  Three batched contractions, same MXU pattern
    as the operator's gradient stage.
    """
    e = u.shape[0]
    n_in = j.shape[1]
    u3 = u.reshape(e, n_in, n_in, n_in)
    u3 = jnp.einsum("ra,etsa->etsr", j, u3, precision=_HI)
    u3 = jnp.einsum("sb,etbr->etsr", j, u3, precision=_HI)
    u3 = jnp.einsum("tc,ecsr->etsr", j, u3, precision=_HI)
    return u3.reshape(e, -1)


def make_transfer_pair(
    prob_f,
    prob_c,
    asm_f: Assembly | None = None,
    asm_c: Assembly | None = None,
    complete_f: Callable | None = None,
    complete_c: Callable | None = None,
) -> tuple[Callable[[jax.Array], jax.Array], Callable[[jax.Array], jax.Array]]:
    """(prolong, restrict) between two assembled levels of one element grid.

    Prolongation is global nodal interpolation: scatter the coarse vector,
    lift with J⊗J⊗J per element, then *average* the (identical) element
    copies back onto fine DOFs — ``P = Z_f^T W_f Ĵ Z_c``.  Restriction is
    built as the exact transpose ``R = P^T = Z_c^T Ĵ^T W_f Z_f`` so the
    V-cycle stays symmetric for PCG.

    ``asm_f`` / ``asm_c`` are the levels' Z/Zᵀ (default:
    :func:`level_assembly` of single-device problems).  ``complete_f`` /
    ``complete_c`` finish each result on its level (default: as it is).  On
    a rank of the sharded path the levels are that rank's views, the
    assemblies its padded boxes', and each completion is the level's sum
    exchange returning the ``(raw, consistent)`` pair: the raw box's
    interior slots are already final, which the overlapped V-cycle reads.
    """
    j = jnp.asarray(
        sem.interpolation_matrix(prob_c.n_degree, prob_f.n_degree), prob_f.dtype
    )
    zf, zc = asm_f or _own_assembly(prob_f), asm_c or _own_assembly(prob_c)
    w_f = prob_f.w_local
    same = lambda v: v

    def prolong(x_c: jax.Array):
        return (complete_f or same)(zf.zt(w_f * tensor3_interp(j, zc.z(x_c))))

    def restrict(r_f: jax.Array):
        return (complete_c or same)(zc.zt(tensor3_interp(j.T, w_f * zf.z(r_f))))

    return prolong, restrict


def make_vcycle(
    operators: Sequence[Callable[[jax.Array], jax.Array]],
    smoothers: Sequence[Callable[[jax.Array], jax.Array]],
    restricts: Sequence[Callable[[jax.Array], jax.Array]],
    prolongs: Sequence[Callable[[jax.Array], jax.Array]],
    coarse_apply: Callable[[jax.Array], jax.Array],
) -> Callable[[jax.Array], jax.Array]:
    """Symmetric V-cycle z = M⁻¹ r over pre-built level callables.

    ``operators``/``smoothers`` cover the smoothed levels 0..L-1 (fine
    first); ``restricts[i]`` maps level i -> i+1, ``prolongs[i]`` back;
    ``coarse_apply`` handles level L outright.  Pre- and post-smoothing use
    the *same* symmetric smoother (Chebyshev–Jacobi with z₀=0 is the fixed
    polynomial q(D⁻¹A)D⁻¹), which with R = P^T makes the whole cycle a
    symmetric linear map — plain PCG stays valid, no flexible CG needed.
    The recursion is a static python unroll: one compiled chain per apply.
    """
    n_smoothed = len(smoothers)

    def cycle(level: int, r: jax.Array) -> jax.Array:
        if level == n_smoothed:
            with obs.scope("pmg.coarse"):
                return coarse_apply(r)
        smooth, op = smoothers[level], operators[level]
        # the level's own work only: its scope never wraps the recursion
        with obs.scope(f"pmg.l{level}"):
            z = smooth(r)                               # pre-smooth (z₀ = 0)
            r_c = restricts[level](r - op(z))
        zc = cycle(level + 1, r_c)
        with obs.scope(f"pmg.l{level}"):
            z = z + prolongs[level](zc)                 # coarse-grid correction
            return z + smooth(r - op(z))                # post-smooth

    return lambda r: cycle(0, r)


def make_vcycle_overlapped(
    operators: Sequence[Callable[[jax.Array], jax.Array]],
    operators_pair: Sequence[Callable[[jax.Array, jax.Array], jax.Array]],
    smoothers: Sequence[Callable[[jax.Array], jax.Array]],
    smoothers_pair: Sequence[Callable[[jax.Array, jax.Array], jax.Array]],
    restricts_pair: Sequence[
        Callable[[jax.Array], tuple[jax.Array, jax.Array]]
    ],
    prolongs_pair: Sequence[
        Callable[[jax.Array], tuple[jax.Array, jax.Array]]
    ],
    coarse_apply_pair: Callable[[jax.Array, jax.Array], jax.Array],
) -> Callable[[jax.Array], jax.Array]:
    """V-cycle with cross-level exchange/compute overlap, bit-identical to
    :func:`make_vcycle`.

    The sharded transfers end in a halo sum-exchange that only rewrites
    face slabs — every interior slot of the *raw* (pre-exchange) restricted
    or prolonged box is already bitwise final.  So each transfer here
    returns the ``(raw, consistent)`` pair instead of the consistent box
    alone, and the next consumer starts its interior element work from
    ``raw``: the coarse level's first smoother A-apply
    (``smoothers_pair`` / ``coarse_apply_pair``, see
    :func:`chebyshev_apply_deferred`) overlaps the restriction exchange,
    and the fine level's post-smooth residual A-apply (``operators_pair``,
    interior gathers from its first argument) overlaps the prolongation
    exchange.  Every deferred operand is bitwise equal to its consistent
    twin on the slots actually read, so the cycle output — and hence PCG
    iteration counts — cannot move.

    ``smoothers_pair[i]`` may ignore its raw argument (Schwarz bases must:
    their expand shells transport face values); that degrades the overlap
    at that level, never the result.
    """
    n_smoothed = len(smoothers)

    def cycle(level: int, raw: jax.Array, con: jax.Array) -> jax.Array:
        if level == n_smoothed:
            with obs.scope("pmg.coarse"):
                return coarse_apply_pair(raw, con)
        with obs.scope(f"pmg.l{level}"):
            z = smoothers_pair[level](raw, con)         # pre-smooth (z₀ = 0)
            raw_c, con_c = restricts_pair[level](con - operators[level](z))
        zc = cycle(level + 1, raw_c, con_c)
        with obs.scope(f"pmg.l{level}"):
            p_raw, p_con = prolongs_pair[level](zc)     # coarse-grid corr.
            resid = con - operators_pair[level](z + p_raw, z + p_con)
            return (z + p_con) + smoothers[level](resid)  # post-smooth

    return lambda r: cycle(0, r, r)


@dataclasses.dataclass(frozen=True)
class PrecondInfo:
    """What make_preconditioner built (for logging/benchmark reporting)."""

    kind: str
    degree: int
    lmax: float | None
    lmin: float | None = None
    levels: tuple[int, ...] | None = None
    smoother: str | None = None
    coarse_op: str | None = None
    overlap: int | None = None
    # compute dtype of the preconditioner chain when it differs from the
    # problem dtype (mixed precision); None = same as the problem
    dtype: str | None = None


def make_pmg_preconditioner(
    prob,
    operator: Callable[[jax.Array], jax.Array],
    *,
    smooth_degree: int | None = None,
    smoother: str = "chebyshev",
    coarse_op: str = "redisc",
    lanczos_iters: int = 10,
    coarse_solve: str = "direct",
    coarse_iters: int = 16,
    ladder: Sequence[int] | None = None,
    schwarz_overlap: int = 1,
    schwarz_inner_degree: int = SCHWARZ_INNER_DEGREE,
    galerkin_matvec: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
) -> tuple[Callable[[jax.Array], jax.Array], PrecondInfo]:
    """Single-shard p-multigrid V-cycle preconditioner.

    Args:
      prob: the fine-level ``PoissonProblem``.
      operator: the fine-level A-apply (assembled storage).
      smooth_degree: Chebyshev stages per smoothing sweep.  Defaults to
        ``PMG_SMOOTH_DEGREE`` for the Jacobi base and the smaller
        ``PMG_SCHWARZ_SMOOTH_DEGREE`` for the Schwarz base (each Schwarz
        application is already a strong smoother).
      smoother: per-level smoother base — "chebyshev" (Chebyshev–Jacobi on
        the Lanczos interval) or "schwarz" (Chebyshev-accelerated
        overlapping Schwarz, the nekRS configuration; spectrum top from
        power iteration, interval [λ_max/PMG_SMOOTH_RATIO, 1.1·λ_max]).
      coarse_op: "redisc" (default) rediscretizes every coarse level on the
        same curved geometry; "galerkin" builds coarse operators as the
        exact triple products ``A_{l+1} = R_l A_l P_l`` applied matrix-free
        through the transfer chain — variationally exact (closes the
        rediscretization gap that caps the small-λ regime) but each coarse
        A-apply recurses to the fine grid, so per-iteration cost grows with
        depth; "galerkin_mat" materializes the *same* triple products once
        at setup into dense per-element blocks (``core.galerkin``), so
        every level below the finest applies the variationally-exact
        operator with one batched element matvec and **zero fine-operator
        applies per coarse apply**.  Smoother diagonals stay the
        rediscretized ones for both Galerkin variants (the standard
        spectrally-equivalent approximation — and what keeps
        "galerkin_mat" iteration-identical to the chained form).
      galerkin_matvec: optional batched element matvec ``(blocks, u) → y``
        for the "galerkin_mat" coarse applies (e.g.
        ``kernels.ops.block_matvec``, the Pallas variant); default is the
        XLA einsum.
      lanczos_iters: Lanczos steps per level for the Chebyshev intervals.
      coarse_solve: coarsest-level treatment — "direct" (dense inverse of
        the degree-1 operator, exact and cheap), "chebyshev" (degree
        ``coarse_iters`` full-interval Chebyshev), or "jacobi"
        (``coarse_iters`` damped-Jacobi sweeps) — all fixed linear
        symmetric maps.
      coarse_iters: iteration count for the iterated coarse solves.
      ladder: explicit degree ladder (default N → ⌈N/2⌉ → … → 1).
      schwarz_overlap / schwarz_inner_degree: Schwarz-smoother knobs
        (see ``core.schwarz.make_schwarz_apply``).

    Returns:
      ``(apply, info)``: the V-cycle application z = M⁻¹r and its
      :class:`PrecondInfo` (fine-level spectrum bounds, ladder, smoother).
    """
    from .operator import coarsen_problem, poisson_assembled

    if smoother not in PMG_SMOOTHERS:
        raise ValueError(
            f"unknown pmg smoother {smoother!r}; choose from {PMG_SMOOTHERS}"
        )
    if coarse_op not in PMG_COARSE_OPS:
        raise ValueError(
            f"unknown pmg coarse_op {coarse_op!r}; choose from {PMG_COARSE_OPS}"
        )
    if smooth_degree is None:
        smooth_degree = pmg_smooth_degree_default(smoother)
    degrees = tuple(ladder) if ladder is not None else pmg_degree_ladder(
        prob.mesh.n_degree
    )
    if len(degrees) < 2:
        raise ValueError(f"pmg ladder needs >= 2 levels, got {degrees}")
    probs = [prob]
    prolongs, restricts = [], []
    for i, nc in enumerate(degrees[1:], 1):
        with obs.span(f"setup.precond.l{i}"):
            probs.append(coarsen_problem(probs[-1], nc))
            p_up, r_down = make_transfer_pair(probs[-2], probs[-1])
        prolongs.append(p_up)
        restricts.append(r_down)

    # Dirichlet masking of the coarse Galerkin applies: the transfer pair
    # preserves the BC subspace (GLL grids share face nodes, so the lifted
    # interpolant's face values depend only on face values), but R = Pᵀ
    # smears interior fine residual onto coarse Dirichlet DOFs — the coarse
    # operator must be mask∘RAP∘mask to stay SPD on its own subspace.
    # Rediscretized levels mask inside poisson_assembled already.
    def _mask_wrap(mask, op):
        if mask is None:
            return op
        return lambda v: mask * op(mask * v)

    ops = [operator]
    if coarse_op == "galerkin_mat":
        # materialize P^T A P once: probe the fine element-local operator
        # for level 1, contract blocks for deeper rungs (core.galerkin).
        # The probing is coefficient-agnostic: variable k rides the folded
        # prob.g and λ(x) rides the screen stream, so the probe consumes
        # exactly the streams the fine operator does.
        from .galerkin import galerkin_block_apply, galerkin_ladder_blocks
        from .operator import screen_stream

        w_eff, lam_eff = screen_stream(prob)
        ladder_blocks = galerkin_ladder_blocks(
            prob.g, prob.d, lam_eff, w_eff, degrees
        )
        for pc_prob, blocks in zip(probs[1:], ladder_blocks):
            ops.append(
                _mask_wrap(
                    pc_prob.mask,
                    galerkin_block_apply(
                        blocks,
                        assembly(
                            pc_prob.l2g, pc_prob.n_degree, pc_prob.mesh.shape
                        ),
                        matvec=galerkin_matvec,
                    ),
                )
            )
    else:
        for i in range(1, len(probs)):
            if coarse_op == "galerkin":
                # A_l = R_{l-1} A_{l-1} P_{l-1}, matrix-free through the
                # chain — every coarse apply recurses to the fine grid
                ops.append(
                    _mask_wrap(
                        probs[i].mask,
                        lambda v, op=ops[-1], r=restricts[i - 1],
                        p=prolongs[i - 1]: r(op(p(v))),
                    )
                )
            else:
                ops.append(poisson_assembled(probs[i]))

    smoothers = []
    lmax0 = lmin0 = None
    for i in range(len(probs) - 1):
        with obs.span(f"setup.precond.l{i}"):
            dinv = masked_dinv(probs[i], assembled_diagonal(probs[i]))
            v0 = masked_seed(
                probs[i], deterministic_seed_vector(probs[i].n_global, dinv.dtype)
            )
            if smoother == "schwarz":
                base = make_schwarz_apply(
                    probs[i],
                    overlap=min(schwarz_overlap, probs[i].mesh.n_degree - 1),
                    inner_degree=schwarz_inner_degree,
                )
            else:
                base = dinv
            lo, lmax_e, lmin_e = smoother_interval(
                ops[i], base, v0, smoother=smoother, lanczos_iters=lanczos_iters
            )
            if i == 0:
                lmax0, lmin0 = float(lmax_e), float(lmin_e)
            smoothers.append(
                chebyshev_apply(
                    ops[i],
                    base,
                    CHEB_SAFETY * lmax_e,
                    lmin=lo,
                    degree=smooth_degree,
                )
            )

    pc, opc = probs[-1], ops[-1]
    with obs.span(f"setup.precond.l{len(probs) - 1}"):
        if coarse_solve == "direct":
            eye = jnp.eye(pc.n_global, dtype=dinv.dtype)
            amat = jax.vmap(opc, in_axes=1, out_axes=1)(eye)
            if pc.mask is not None:
                # the masked coarse operator has zero rows/columns on Dirichlet
                # DOFs; put 1 there so the inverse exists, then project the
                # apply — exactly the subspace inverse, identity-free outside
                amat = amat + jnp.diag(1.0 - pc.mask.astype(amat.dtype))
            ainv = jnp.linalg.inv(amat)
            if pc.mask is None:
                coarse_apply = lambda r: jnp.dot(ainv, r, precision=_HI)
            else:
                coarse_apply = lambda r: pc.mask * jnp.dot(
                    ainv, pc.mask * r, precision=_HI
                )
        elif coarse_solve in ("chebyshev", "jacobi"):
            dinv_c = masked_dinv(pc, assembled_diagonal(pc))
            if coarse_solve == "chebyshev":
                v0 = masked_seed(
                    pc, deterministic_seed_vector(pc.n_global, dinv_c.dtype)
                )
                lmin_e, lmax_e = lanczos_extremes(opc, dinv_c, v0, iters=lanczos_iters)
                coarse_apply = chebyshev_apply(
                    opc,
                    dinv_c,
                    CHEB_SAFETY * lmax_e,
                    lmin=CHEB_LMIN_SAFETY * lmin_e,
                    degree=coarse_iters,
                )
            else:

                def coarse_apply(r: jax.Array) -> jax.Array:
                    # damped-Jacobi sweeps from z₀=0: a fixed polynomial in
                    # D⁻¹A, hence linear and symmetric like the other choices
                    z = (2.0 / 3.0) * dinv_c * r
                    for _ in range(coarse_iters - 1):
                        z = z + (2.0 / 3.0) * dinv_c * (r - opc(z))
                    return z

        else:
            raise ValueError(
                f"unknown pmg coarse_solve {coarse_solve!r}; "
                "choose direct | chebyshev | jacobi"
            )

    apply = make_vcycle(ops[:-1], smoothers, restricts, prolongs, coarse_apply)
    return apply, PrecondInfo(
        "pmg",
        smooth_degree,
        lmax0,
        lmin0,
        degrees,
        smoother=smoother,
        coarse_op=coarse_op,
        overlap=schwarz_overlap if smoother == "schwarz" else None,
    )


def cast_apply(
    apply: Callable[[jax.Array], jax.Array], compute_dtype, out_dtype
) -> Callable[[jax.Array], jax.Array]:
    """Wrap an apply with the mixed-precision cast boundary.

    The returned callable rounds its input to ``compute_dtype``, runs the
    wrapped chain there, and widens the result back to ``out_dtype`` — the
    single pair of casts the whole mixed-precision preconditioner needs
    (everything inside already lives in ``compute_dtype``).
    """
    cdt, odt = jnp.dtype(compute_dtype), jnp.dtype(out_dtype)
    return lambda r: apply(r.astype(cdt)).astype(odt)


# make_preconditioner knobs that shape the built setup, with their defaults.
# Callable knobs (fused_d_update, galerkin_matvec) are kernel substitutions —
# they change how a stage is computed, never what it computes — so they are
# deliberately NOT part of the signature.
_SIGNATURE_DEFAULTS = {
    "degree": 2,
    "power_iters": 15,
    "lanczos_iters": 10,
    "lmin_source": "lanczos",
    "pmg_smooth_degree": None,
    "pmg_smoother": "chebyshev",
    "pmg_coarse_op": "redisc",
    "pmg_coarse_solve": "direct",
    "pmg_coarse_iters": 16,
    "pmg_ladder": None,
    "schwarz_overlap": 1,
    "schwarz_weighting": "sqrt",
    "schwarz_inner_degree": SCHWARZ_INNER_DEGREE,
    "precond_dtype": None,
}


def precond_signature(kind: str, **kwargs) -> tuple:
    """Canonical hashable signature of a :func:`make_preconditioner` config.

    Every knob that affects the *built setup* is normalized (defaults
    filled in, ladder tuples frozen, dtypes resolved to their names) and
    emitted in a fixed order, so two calls that would build the same
    preconditioner produce equal signatures whatever subset of knobs they
    spelled out — the keying contract ``core.solver_cache`` relies on.
    Unknown knobs raise instead of being silently dropped (a typo must not
    alias two different configs to one cache slot).
    """
    if kind not in PRECOND_KINDS:
        raise ValueError(f"unknown precond {kind!r}; choose from {PRECOND_KINDS}")
    unknown = set(kwargs) - set(_SIGNATURE_DEFAULTS)
    if unknown:
        raise ValueError(
            f"unknown preconditioner knob(s) {sorted(unknown)}; "
            f"known: {sorted(_SIGNATURE_DEFAULTS)}"
        )
    merged = {**_SIGNATURE_DEFAULTS, **kwargs}
    if merged["pmg_ladder"] is not None:
        merged["pmg_ladder"] = tuple(int(d) for d in merged["pmg_ladder"])
    if merged["precond_dtype"] is not None:
        merged["precond_dtype"] = jnp.dtype(merged["precond_dtype"]).name
    return (("kind", kind),) + tuple(
        (name, merged[name]) for name in sorted(_SIGNATURE_DEFAULTS)
    )


@obs.spanned("setup.precond")
def make_preconditioner(
    kind: str,
    prob,
    operator: Callable[[jax.Array], jax.Array],
    *,
    degree: int = 2,
    power_iters: int = 15,
    lanczos_iters: int = 10,
    lmin_source: str = "lanczos",
    fused_d_update: Callable[..., jax.Array] | None = None,
    pmg_smooth_degree: int | None = None,
    pmg_smoother: str = "chebyshev",
    pmg_coarse_op: str = "redisc",
    pmg_coarse_solve: str = "direct",
    pmg_coarse_iters: int = 16,
    pmg_ladder: Sequence[int] | None = None,
    schwarz_overlap: int = 1,
    schwarz_weighting: str = "sqrt",
    schwarz_inner_degree: int = SCHWARZ_INNER_DEGREE,
    galerkin_matvec: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
    precond_dtype=None,
) -> tuple[Callable[[jax.Array], jax.Array] | None, PrecondInfo]:
    """Build a single-device assembled-path preconditioner by name.

    Args:
      kind: "none" | "jacobi" | "chebyshev" | "schwarz" | "pmg".
      prob: the ``PoissonProblem``.
      operator: the assembled A-apply the preconditioner wraps.
      degree: standalone-Chebyshev polynomial degree.
      power_iters / lanczos_iters: spectrum-estimation step budget.  For
        "chebyshev", ``lmin_source="lanczos"`` (default) estimates *both*
        interval ends with ``lanczos_iters`` Lanczos steps; ``"ratio"``
        reproduces the legacy fixed λ_max/CHEB_LMIN_RATIO lower bound
        (with ``power_iters`` power-iteration steps for λ_max).
      fused_d_update: optional Pallas streaming fusion for the Chebyshev
        d-update (kernels.ops.fused_cheb_d_update).
      pmg_*: p-multigrid knobs, forwarded to
        :func:`make_pmg_preconditioner` (``pmg_smooth_degree`` is the
        per-level smoother degree; ``degree`` stays the standalone knob;
        ``pmg_coarse_op="galerkin_mat"`` materializes the PᵀAP coarse
        operators into per-element blocks — ``core.galerkin``).
      galerkin_matvec: optional batched element matvec for the
        "galerkin_mat" coarse applies (``kernels.ops.block_matvec``).
      schwarz_*: overlapping-Schwarz knobs — extension width in GLL nodes
        (``schwarz_overlap``, 0 = block Jacobi), partition-of-unity
        weighting ("sqrt" symmetric default; "post" = RAS, nonsymmetric,
        rejected here because plain PCG needs a symmetric M), and the
        in-eigenbasis block-solve Chebyshev degree
        (``schwarz_inner_degree``).  Shared by kind="schwarz" and the
        pmg smoother="schwarz".
      precond_dtype: compute dtype of the *entire* preconditioner chain
        (default None = the problem dtype).  Passing e.g. ``jnp.float32``
        inside an fp64 solve rebuilds every preconditioner ingredient —
        A-applies, diagonals, Chebyshev recurrences, Schwarz FDM blocks,
        pMG levels and transfers — from an fp32 cast of the problem
        (``operator.cast_problem``), wraps the result in one
        :func:`cast_apply` boundary, and roughly halves preconditioner
        bandwidth.  The fp32 apply is only approximately symmetric in fp64
        arithmetic, so pair it with ``cg_assembled(cg_variant="flexible")``
        for robustness near tight tolerances.  The caller's ``operator``
        is NOT used inside the mixed chain (it computes in the problem
        dtype); it still defines the outer solve.

    Returns:
      ``(apply, info)``; ``apply`` is None for "none" (plain CG), else the
      z = M⁻¹r application, always a symmetric linear map (PCG-valid) —
      symmetric to working precision only under ``precond_dtype``.
    """
    if kind not in PRECOND_KINDS:
        raise ValueError(f"unknown precond {kind!r}; choose from {PRECOND_KINDS}")
    if kind == "none":
        return None, PrecondInfo("none", 0, None)
    if precond_dtype is not None and jnp.dtype(precond_dtype) != jnp.dtype(
        prob.dtype
    ):
        from .operator import cast_problem, poisson_assembled

        prob_c = cast_problem(prob, precond_dtype)
        inner, info = make_preconditioner(
            kind,
            prob_c,
            poisson_assembled(prob_c),
            degree=degree,
            power_iters=power_iters,
            lanczos_iters=lanczos_iters,
            lmin_source=lmin_source,
            fused_d_update=fused_d_update,
            pmg_smooth_degree=pmg_smooth_degree,
            pmg_smoother=pmg_smoother,
            pmg_coarse_op=pmg_coarse_op,
            pmg_coarse_solve=pmg_coarse_solve,
            pmg_coarse_iters=pmg_coarse_iters,
            pmg_ladder=pmg_ladder,
            schwarz_overlap=schwarz_overlap,
            schwarz_weighting=schwarz_weighting,
            schwarz_inner_degree=schwarz_inner_degree,
            galerkin_matvec=galerkin_matvec,
        )
        return (
            cast_apply(inner, precond_dtype, prob.dtype),
            dataclasses.replace(info, dtype=jnp.dtype(precond_dtype).name),
        )
    if kind == "pmg":
        return make_pmg_preconditioner(
            prob,
            operator,
            smooth_degree=pmg_smooth_degree,
            smoother=pmg_smoother,
            coarse_op=pmg_coarse_op,
            lanczos_iters=lanczos_iters,
            coarse_solve=pmg_coarse_solve,
            coarse_iters=pmg_coarse_iters,
            ladder=pmg_ladder,
            schwarz_overlap=schwarz_overlap,
            schwarz_inner_degree=schwarz_inner_degree,
            galerkin_matvec=galerkin_matvec,
        )
    if kind == "schwarz":
        if schwarz_weighting == "post":
            raise ValueError(
                "schwarz weighting='post' (RAS) is nonsymmetric; plain PCG "
                "needs the symmetric 'sqrt' (or 'none') weighting — use "
                "make_schwarz_apply directly for Richardson/flexible solvers"
            )
        apply = make_schwarz_apply(
            prob,
            overlap=schwarz_overlap,
            weighting=schwarz_weighting,
            inner_degree=schwarz_inner_degree,
        )
        return apply, PrecondInfo(
            "schwarz", schwarz_inner_degree, None, overlap=schwarz_overlap
        )
    diag = assembled_diagonal(prob)
    dinv = masked_dinv(prob, diag)
    if kind == "jacobi":
        return jacobi_apply(dinv), PrecondInfo("jacobi", 1, None)
    v0 = masked_seed(prob, deterministic_seed_vector(prob.n_global, diag.dtype))
    if lmin_source == "lanczos":
        lmin_e, lmax_e = lanczos_extremes(operator, dinv, v0, iters=lanczos_iters)
        lmax = CHEB_SAFETY * lmax_e
        lmin = CHEB_LMIN_SAFETY * lmin_e
    elif lmin_source == "ratio":
        lmax = CHEB_SAFETY * power_lambda_max(operator, dinv, v0, iters=power_iters)
        lmin = None
    else:
        raise ValueError(f"unknown lmin_source {lmin_source!r}")
    apply = chebyshev_apply(
        operator, dinv, lmax, lmin=lmin, degree=degree,
        fused_d_update=fused_d_update,
    )
    return apply, PrecondInfo(
        "chebyshev", degree, float(lmax), None if lmin is None else float(lmin)
    )
