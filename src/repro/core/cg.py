"""Preconditioned Conjugate Gradient in hipBone-assembled and NekBone-scattered form.

One PCG implementation serves every solver path; plain CG is PCG with the
identity preconditioner, and in that case the preconditioner stage folds
away so the compiled program is exactly the seed's CG (same reductions,
same fusion schedule):

  * one fused pass computes ``r_{j+1} = r_j - α A p`` AND accumulates
    ``r_{j+1}·r_{j+1}`` (paper: "Fusing this reduction with the update of r
    avoids the need for a separate kernel to read the vector r again");
  * with a preconditioner, a second fused pass computes ``z = M⁻¹ r`` AND
    accumulates ``r·z`` (the same streaming trick applied to the PCG
    inner product — kernels/streams.py has the Pallas version);
  * the AXPY ``x += α p`` carries no data dependence on the reductions, so
    XLA may overlap the cross-device psums with it — the paper's
    allreduce-hiding trick, expressed as dataflow;
  * inner products on assembled vectors are plain (unweighted) dots.

The scattered baseline replicates NekBone: vectors of length N_L, weighted
inner products reading the extra W vector, and a combined ZZ^T
gather-scatter inside the operator.

Iteration control: a fixed count (NekBone uses 100) runs under ``lax.scan``
so a single compiled program covers the whole benchmark; passing ``tol``
switches to ``lax.while_loop`` stopping at ‖r‖ ≤ tol·‖r₀‖ (capped at
``n_iter``), with ``CGResult.iterations`` reporting the count actually run.

Solver guardrails: every iteration the loop inspects the scalars it already
reduces (p·Ap, r·z, r·r) for breakdown — NaN/Inf residual, indefinite
curvature (p·Ap ≤ 0) or indefinite preconditioner (r·z < 0), divergence
(rdotr > ``divergence_factor`` · rdotr₀) and stagnation (no relative
reduction of the best-seen rdotr by ``stagnation_rtol`` within
``stagnation_window`` iterations).  In tolerance mode a tripped detector
exits the while-loop on that iteration; in fixed-count mode (no early exit
under ``lax.scan``) the first failure is recorded and reported.  Every
detector input is an already-allreduced scalar, so under ``shard_map`` all
replicas see the same flag and exit on the same iteration — no extra
collective is added, and a healthy solve runs the exact same iterations as
before.  The outcome is ``CGResult.status``, a jit-safe ``SolveStatus``
code (see its docstring for the enum contract).

CG variants: the default ``cg_variant="standard"`` uses the Fletcher–Reeves
β = (r·z)_new/(r·z)_old, which assumes M⁻¹ is a *fixed symmetric* linear
map.  ``cg_variant="flexible"`` switches β to the Polak–Ribière form
β = z_new·(r_new − r_old)/(r·z)_old (flexible CG, Notay 2000) — robust to
preconditioners that are only approximately symmetric in the outer dtype's
arithmetic, e.g. an fp32 V-cycle or Schwarz apply inside an fp64 solve
(precond.make_preconditioner(precond_dtype=...)).  The extra cost is one
inner product per iteration, fused into the existing allreduce as a
length-2 payload.
"""
from __future__ import annotations

import enum
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


# TPU's default f32 matmul is one bf16 pass; the solver needs full f32
_HI = jax.lax.Precision.HIGHEST

__all__ = [
    "CGResult",
    "CG_VARIANTS",
    "DIVERGENCE_FACTOR",
    "STAGNATION_RTOL",
    "STAGNATION_WINDOW",
    "SolveStatus",
    "batched_cg_assembled",
    "cg_assembled",
    "cg_scattered",
    "fused_residual_update",
    "status_name",
]

CG_VARIANTS = ("standard", "flexible")

# Detector defaults (override per solve; None disables that detector).
# divergence: rdotr is the *squared* residual norm, so 1e6 means the
# residual grew 1000× over r₀ — far outside healthy CG oscillation (which
# stays within ~√cond(A) of r₀) and small enough to outrace the stagnation
# window on an exponentially blowing-up solve.
# stagnation: a healthy tol-mode solve reduces its best-seen rdotr by ≫1 %
# well within any 50-iteration window; a solve pinned at a noise floor
# (corrupted operator bits, rank-deficient M⁻¹) does not.
DIVERGENCE_FACTOR = 1e6
STAGNATION_WINDOW = 50
STAGNATION_RTOL = 0.99

# in-loop sentinel; never escapes into CGResult.status
_RUNNING = -1


class SolveStatus(enum.IntEnum):
    """Terminal state of a (P)CG solve — `CGResult.status`.

    * ``CONVERGED`` — ‖r‖ ≤ tol·‖r₀‖ (tolerance mode), including the
      rdotr₀ = 0 edge case (zero RHS / exact x₀: 0 iterations).
    * ``MAX_ITER`` — the iteration budget ran out before the tolerance was
      met.  In fixed-count mode (``tol=None``) there is no tolerance to
      certify, so MAX_ITER is the *normal* completion status there (unless
      rdotr₀ = 0, which still reports CONVERGED at 0 iterations).
    * ``BREAKDOWN_NAN`` — a non-finite reduction scalar (NaN/Inf residual
      or p·Ap): bit corruption, overflow, or a NaN in the operator chain.
    * ``BREAKDOWN_INDEFINITE`` — p·Ap ≤ 0 (operator not positive-definite
      on the Krylov space) or r·z < 0 (preconditioner not positive-
      definite, e.g. a sign-flipped M⁻¹).
    * ``STAGNATED`` — best-seen rdotr not reduced by ``stagnation_rtol``
      for ``stagnation_window`` consecutive iterations (tolerance mode
      only).
    * ``DIVERGED`` — rdotr > ``divergence_factor`` · rdotr₀ (tolerance
      mode only).  ``divergence_factor`` applies to rdotr, the *squared*
      residual norm.

    Codes are small non-negative ints carried through jit as int32;
    ``status_name`` maps a code to its lowercase wire name (the form
    benchmark records and logs use).
    """

    CONVERGED = 0
    MAX_ITER = 1
    BREAKDOWN_NAN = 2
    BREAKDOWN_INDEFINITE = 3
    STAGNATED = 4
    DIVERGED = 5


def status_name(code: int | jax.Array) -> str:
    """Lowercase wire name of a `SolveStatus` code (e.g. ``"converged"``)."""
    return SolveStatus(int(code)).name.lower()


class CGResult(NamedTuple):
    x: jax.Array
    rdotr: jax.Array
    iterations: jax.Array
    status: jax.Array
    rdotr_history: jax.Array | None


def fused_residual_update(
    r: jax.Array, ap: jax.Array, alpha: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """One-pass r update + self-dot (reference; Pallas version in kernels/)."""
    r_new = r - alpha * ap
    return r_new, jnp.vdot(r_new, r_new, precision=_HI)


def _dot(a: jax.Array, b: jax.Array, w: jax.Array | None) -> jax.Array:
    if w is None:
        return jnp.vdot(a, b, precision=_HI)
    return jnp.vdot(a * w, b, precision=_HI)


def _safe_div(a, b):
    # fixed-iteration CG (NekBone runs exactly 100) keeps iterating after
    # convergence; guard 0/0 so x simply freezes at the solution
    return jnp.where(b != 0, a / jnp.where(b != 0, b, 1), 0.0)


def _pcg(
    operator: Callable[[jax.Array], jax.Array],
    b: jax.Array,
    x0: jax.Array | None,
    *,
    n_iter: int,
    tol: float | None,
    weight: jax.Array | None,
    psum: Callable[[jax.Array], jax.Array] | None,
    precond: Callable[[jax.Array], jax.Array] | None,
    fused_update: Callable[..., tuple[jax.Array, jax.Array]] | None,
    fused_precond_dot: Callable[..., tuple[jax.Array, jax.Array]] | None,
    record_history: bool,
    variant: str = "standard",
    divergence_factor: float | None = DIVERGENCE_FACTOR,
    stagnation_window: int | None = STAGNATION_WINDOW,
    stagnation_rtol: float = STAGNATION_RTOL,
) -> CGResult:
    if variant not in CG_VARIANTS:
        raise ValueError(
            f"unknown cg_variant {variant!r}; choose from {CG_VARIANTS}"
        )
    if isinstance(precond, str):
        raise TypeError(
            f"precond must be a callable z = M⁻¹r (or None), got the string "
            f"{precond!r}; build one with core.precond.make_preconditioner "
            f"(string kinds are only accepted by distributed.dist_cg)"
        )
    if fused_precond_dot is not None and precond is None:
        raise ValueError(
            "fused_precond_dot given without precond; pass the (unfused) "
            "apply as precond too — it gates the PCG recurrence"
        )
    allsum = psum or (lambda v: v)
    upd = fused_update or fused_residual_update
    # without a preconditioner z_new == r_new, so Polak–Ribière reduces to
    # Fletcher–Reeves up to the (exactly-orthogonal) r_new·r_old term — keep
    # the cheaper standard recurrence there
    flexible = variant == "flexible" and precond is not None
    x = jnp.zeros_like(b) if x0 is None else x0

    def apply_precond(r_vec):
        """z = M⁻¹r and the local part of r·z, in one fused pass if given."""
        if precond is None:
            raise AssertionError("apply_precond called without a preconditioner")
        if fused_precond_dot is not None:
            return fused_precond_dot(r_vec)
        z_vec = precond(r_vec)
        return z_vec, _dot(r_vec, z_vec, weight)

    r = b - operator(x)
    rdotr0 = allsum(_dot(r, r, weight))
    if precond is None:
        z, rz = r, rdotr0
    else:
        z, rz_local = apply_precond(r)
        rz = allsum(rz_local)
    p = z

    # Guardrails: status codes as int32 scalars so they live in the loop
    # carry.  Detector inputs (pap, rz, rdotr) are already allreduced, so
    # under shard_map every replica computes the same flag — replicas stay
    # in lockstep with zero added collectives.
    run = jnp.asarray(_RUNNING, jnp.int32)
    converged = jnp.asarray(SolveStatus.CONVERGED, jnp.int32)
    max_iter_ = jnp.asarray(SolveStatus.MAX_ITER, jnp.int32)
    nan_code = jnp.asarray(SolveStatus.BREAKDOWN_NAN, jnp.int32)
    indef_code = jnp.asarray(SolveStatus.BREAKDOWN_INDEFINITE, jnp.int32)

    def detect(pap, rz_new, rdotr_pre, rdotr_new):
        """NaN/indefinite breakdown code for one iteration, else _RUNNING.

        ``rdotr_pre > 0`` guards the indefinite test: a fixed-count solve
        keeps stepping after convergence with p ≈ 0, where p·Ap = 0 is not
        a breakdown.
        """
        bad = ~jnp.isfinite(rdotr_new) | ~jnp.isfinite(pap)
        indef = ((pap <= 0) | (rz_new < 0)) & (rdotr_pre > 0)
        return jnp.where(bad, nan_code, jnp.where(indef, indef_code, run))

    # pre-loop breakdowns: non-finite b/x0/operator, or an indefinite M⁻¹
    # visible in r·M⁻¹r before the first step
    status0 = jnp.where(
        ~jnp.isfinite(rdotr0),
        nan_code,
        jnp.where(rz < 0, indef_code, run),
    )

    def step(x, r, p, rz, rdotr):
        ap = operator(p)
        pap = allsum(_dot(p, ap, weight))
        alpha = _safe_div(rz, pap)
        if weight is None:
            # hipBone fusion: r-update + local reduction in one pass...
            r_new, rr_local = upd(r, ap, alpha)
        else:
            r_new = r - alpha * ap
            rr_local = _dot(r_new, r_new, weight)
        # ...and x-update independent of the psum -> overlappable allreduce.
        x_new = x + alpha * p
        rdotr_new = allsum(rr_local)
        if precond is None:
            z_new, rz_new = r_new, rdotr_new
            beta = _safe_div(rz_new, rz)
        elif flexible:
            # Polak–Ribière β = z_new·(r_new − r_old)/rz_old; the extra
            # z_new·r_old dot rides the same allreduce as r_new·z_new
            z_new, rz_local = apply_precond(r_new)
            pair = allsum(jnp.stack([rz_local, _dot(z_new, r, weight)]))
            rz_new = pair[0]
            beta = _safe_div(rz_new - pair[1], rz)
        else:
            z_new, rz_local = apply_precond(r_new)
            rz_new = allsum(rz_local)
            beta = _safe_div(rz_new, rz)
        p_new = z_new + beta * p
        fail = detect(pap, rz_new, rdotr, rdotr_new)
        return x_new, r_new, p_new, rz_new, rdotr_new, fail

    zero_rhs = rdotr0 == 0

    if tol is None:
        # lax.scan cannot exit early (and the sharded fixed-count path
        # relies on scan for shard_map's check_rep) — record the *first*
        # breakdown and keep stepping; _safe_div keeps the post-breakdown
        # arithmetic inert where it can.
        def body(carry, _):
            x, r, p, rz, rdotr, status = carry
            x, r, p, rz, rdotr, fail = step(x, r, p, rz, rdotr)
            status = jnp.where(status == run, fail, status)
            return (x, r, p, rz, rdotr, status), rdotr

        (x, r, p, rz, rdotr, status), hist = jax.lax.scan(
            body, (x, r, p, rz, rdotr0, status0), None, length=n_iter
        )
        status = jnp.where(
            status == run, jnp.where(zero_rhs, converged, max_iter_), status
        )
        return CGResult(
            x=x,
            rdotr=rdotr,
            iterations=jnp.where(zero_rhs, 0, n_iter),
            status=status,
            rdotr_history=hist if record_history else None,
        )

    # tolerance mode: ‖r‖ ≤ tol·‖r₀‖, capped at n_iter; the history buffer
    # (and its per-iteration scatter) only enters the carry when asked for
    target = jnp.asarray(tol, rdotr0.dtype) ** 2 * rdotr0
    hist0 = (jnp.zeros((n_iter,), rdotr0.dtype),) if record_history else ()
    diverged_code = jnp.asarray(SolveStatus.DIVERGED, jnp.int32)
    stagnated_code = jnp.asarray(SolveStatus.STAGNATED, jnp.int32)

    def cond(carry):
        rdotr, k, status = carry[4], carry[5], carry[6]
        return (k < n_iter) & (rdotr > target) & (status == run)

    def wbody(carry):
        x, r, p, rz, rdotr, k, status, best, since = carry[:9]
        x, r, p, rz, rdotr_new, fail = step(x, r, p, rz, rdotr)
        if divergence_factor is not None:
            div = rdotr_new > jnp.asarray(
                divergence_factor, rdotr0.dtype
            ) * rdotr0
            fail = jnp.where((fail == run) & div, diverged_code, fail)
        if stagnation_window is not None:
            improved = rdotr_new < jnp.asarray(
                stagnation_rtol, rdotr0.dtype
            ) * best
            since = jnp.where(improved, 0, since + 1)
            best = jnp.minimum(best, rdotr_new)
            fail = jnp.where(
                (fail == run) & (since >= stagnation_window),
                stagnated_code,
                fail,
            )
        # cond guarantees status == run on entry, so fail IS the new status
        hist = (carry[9].at[k].set(rdotr_new),) if record_history else ()
        return (x, r, p, rz, rdotr_new, k + 1, fail, best, since) + hist

    out = jax.lax.while_loop(
        cond,
        wbody,
        (x, r, p, rz, rdotr0, jnp.asarray(0), status0, rdotr0,
         jnp.asarray(0)) + hist0,
    )
    rdotr, k, status = out[4], out[5], out[6]
    status = jnp.where(
        status == run,
        jnp.where(rdotr <= target, converged, max_iter_),
        status,
    )
    return CGResult(
        x=out[0],
        rdotr=rdotr,
        iterations=k,
        status=status,
        rdotr_history=out[9] if record_history else None,
    )


def cg_assembled(
    operator: Callable[[jax.Array], jax.Array],
    b_g: jax.Array,
    x0: jax.Array | None = None,
    *,
    n_iter: int = 100,
    tol: float | None = None,
    psum: Callable[[jax.Array], jax.Array] | None = None,
    precond: Callable[[jax.Array], jax.Array] | None = None,
    fused_update: Callable[..., tuple[jax.Array, jax.Array]] | None = None,
    fused_precond_dot: Callable[..., tuple[jax.Array, jax.Array]] | None = None,
    record_history: bool = False,
    cg_variant: str = "standard",
    divergence_factor: float | None = DIVERGENCE_FACTOR,
    stagnation_window: int | None = STAGNATION_WINDOW,
    stagnation_rtol: float = STAGNATION_RTOL,
) -> CGResult:
    """hipBone (P)CG on assembled (length N_G) vectors; unweighted dots.

    ``precond``: optional z = M⁻¹r application (see core.precond); None
    gives the seed's plain CG.  ``fused_precond_dot``: optional one-pass
    (M⁻¹r, r·M⁻¹r) — the Pallas streaming fusion of the PCG inner product.
    ``tol``: stop at ‖r‖ ≤ tol·‖r₀‖ instead of running n_iter iterations.
    ``cg_variant``: "standard" (Fletcher–Reeves β, exact-symmetric M⁻¹) or
    "flexible" (Polak–Ribière β, robust to inexactly-symmetric appliers
    such as mixed-precision preconditioners — see module docstring).

    Guardrail knobs (see `SolveStatus` and the module docstring):
    ``divergence_factor`` trips DIVERGED at rdotr > factor·rdotr₀ and
    ``stagnation_window``/``stagnation_rtol`` trip STAGNATED after a
    window without relative progress — both tolerance-mode only; pass
    None to disable either detector.  NaN and indefinite breakdown
    detection is always on.  The outcome lands in ``CGResult.status``.
    """
    return _pcg(
        operator,
        b_g,
        x0,
        n_iter=n_iter,
        tol=tol,
        weight=None,
        psum=psum,
        precond=precond,
        fused_update=fused_update,
        fused_precond_dot=fused_precond_dot,
        record_history=record_history,
        variant=cg_variant,
        divergence_factor=divergence_factor,
        stagnation_window=stagnation_window,
        stagnation_rtol=stagnation_rtol,
    )


def batched_cg_assembled(
    operator: Callable[[jax.Array], jax.Array],
    b_block: jax.Array,
    x0: jax.Array | None = None,
    *,
    n_iter: int = 100,
    tol: float | None = None,
    precond: Callable[[jax.Array], jax.Array] | None = None,
    fused_update: Callable[..., tuple[jax.Array, jax.Array]] | None = None,
    fused_precond_dot: Callable[..., tuple[jax.Array, jax.Array]] | None = None,
    record_history: bool = False,
    cg_variant: str = "standard",
    divergence_factor: float | None = DIVERGENCE_FACTOR,
    stagnation_window: int | None = STAGNATION_WINDOW,
    stagnation_rtol: float = STAGNATION_RTOL,
) -> CGResult:
    """Multi-RHS (P)CG: solve ``A x_i = b_i`` for every row of ``b_block``.

    The batched front end of the solver service (ROADMAP "millions of
    users" direction): ``b_block`` is a ``(B, n_global)`` block of
    right-hand sides sharing ONE operator and ONE preconditioner setup —
    every setup cost (assembled diagonals, Lanczos intervals, Schwarz FDM
    eigendecompositions, Galerkin blocks) is paid once and amortized over
    the batch, and the B solves run as a single compiled program whose
    vector stages stream ``(B, n)`` blocks instead of B separate ``(n,)``
    passes.

    Implementation: :func:`cg_assembled` vmapped over the leading batch
    dimension.  ``jax.vmap`` of ``lax.while_loop`` runs the loop while ANY
    column is still active and freezes finished columns with masked
    (``select``) carry updates, so every column independently stops at
    ``tol`` — per-column ``iterations`` and ``status`` are *bit-identical*
    to B standalone :func:`cg_assembled` calls (the zero-RHS column
    short-circuit included: a zero row reports CONVERGED at 0 iterations).
    Already-converged columns ride along masked (their carries are frozen,
    not recomputed), so a batch mixing easy and hard RHS costs the max
    column's iterations, not the sum.

    Args:
      operator: single-column A-apply ``(n,) -> (n,)`` (batching is
        applied here — pass the same apply a standalone solve would use).
      b_block: ``(B, n_global)`` RHS block.
      x0: optional ``(B, n_global)`` initial guesses.
      precond / fused_update / fused_precond_dot: single-column callables,
        exactly as :func:`cg_assembled` takes them; they are vmapped along
        with the loop.
      Everything else: as :func:`cg_assembled` (shared by all columns;
        per-column tolerances are a grouping concern — the serving engine
        batches only requests that share them).

    Returns:
      ``CGResult`` with batched leaves: ``x`` ``(B, n)``, ``rdotr`` /
      ``iterations`` / ``status`` ``(B,)``, and ``rdotr_history``
      ``(B, n_iter)`` when ``record_history`` (frozen columns repeat their
      final value in unreached slots).
    """
    if b_block.ndim != 2:
        raise ValueError(
            f"b_block must be (B, n_global), got shape {b_block.shape}; "
            "for a single RHS use cg_assembled (or pass b[None, :])"
        )
    if x0 is not None and x0.shape != b_block.shape:
        raise ValueError(
            f"x0 shape {x0.shape} must match b_block shape {b_block.shape}"
        )

    def solve_one(b_i, x0_i):
        return cg_assembled(
            operator,
            b_i,
            x0_i,
            n_iter=n_iter,
            tol=tol,
            precond=precond,
            fused_update=fused_update,
            fused_precond_dot=fused_precond_dot,
            record_history=record_history,
            cg_variant=cg_variant,
            divergence_factor=divergence_factor,
            stagnation_window=stagnation_window,
            stagnation_rtol=stagnation_rtol,
        )

    if x0 is None:
        return jax.vmap(lambda b_i: solve_one(b_i, None))(b_block)
    return jax.vmap(solve_one)(b_block, x0)


def cg_scattered(
    operator: Callable[[jax.Array], jax.Array],
    b_l: jax.Array,
    w_local: jax.Array,
    x0: jax.Array | None = None,
    *,
    n_iter: int = 100,
    tol: float | None = None,
    psum: Callable[[jax.Array], jax.Array] | None = None,
    precond: Callable[[jax.Array], jax.Array] | None = None,
    record_history: bool = False,
    cg_variant: str = "standard",
    divergence_factor: float | None = DIVERGENCE_FACTOR,
    stagnation_window: int | None = STAGNATION_WINDOW,
    stagnation_rtol: float = STAGNATION_RTOL,
) -> CGResult:
    """NekBone baseline (P)CG on scattered (length N_L) vectors; weighted dots."""
    return _pcg(
        operator,
        b_l,
        x0,
        n_iter=n_iter,
        tol=tol,
        weight=w_local,
        psum=psum,
        precond=precond,
        fused_update=None,
        fused_precond_dot=None,
        record_history=record_history,
        variant=cg_variant,
        divergence_factor=divergence_factor,
        stagnation_window=stagnation_window,
        stagnation_rtol=stagnation_rtol,
    )
