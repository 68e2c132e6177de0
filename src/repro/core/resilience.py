"""Host-side solver guardrails: retry policy and the fallback chain.

`core.cg` detects *that* a solve failed (``CGResult.status``); this module
decides *what to do next*.  On a non-CONVERGED status the chain escalates
through progressively more conservative configurations, re-solving after
each step, until the solve converges or the chain is exhausted:

1. ``retry`` — re-run the same configuration once: a transient fault
   (SDC bit-flip, one corrupted payload) does not recur, and no amount of
   configuration degradation would have been the right response to it;
2. ``flexible_cg`` — switch the β recurrence to Polak–Ribière (tolerates
   an inexactly-symmetric M⁻¹, the usual first casualty of a degraded
   preconditioner chain);
3. ``full_precision_precond`` — drop ``precond_dtype`` back to the solve
   dtype (an fp32 chain that stalls below tol is healed by this rung);
4. ``downgrade_precond`` — step down the preconditioner ladder one rung at
   a time (`PRECOND_DOWNGRADE`: pmg → chebyshev → jacobi → none; schwarz
   also falls back to chebyshev), ending at plain CG with no M⁻¹ at all.

Every attempt is recorded machine-readably (`SolveAttempt` /
`FallbackResult.record`) so a serving layer can log exactly what was tried
and why.  Attempts restart from the caller's x₀ — a failed attempt's
iterate may be NaN or garbage, so nothing is warm-started from it.

`run_fallback_chain` is the generic engine (bring your own solve
callable — the sharded paths use it with `distributed.dist_cg`);
`solve_with_fallback` is the single-device assembled-path convenience that
rebuilds the preconditioner via `core.precond.make_preconditioner` at each
rung.  The operator kernel is not part of the chain: the selection
policy (``kernels.ops.should_fuse_operator``) never picks a kernel the
backend cannot lower, and an explicit request for one raises.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import numpy as np

from .cg import CGResult, SolveStatus, cg_assembled, status_name
from .operator import PoissonProblem, poisson_assembled
from .precond import make_preconditioner

__all__ = [
    "PRECOND_DOWNGRADE",
    "FallbackResult",
    "SolveAttempt",
    "run_fallback_chain",
    "solve_with_fallback",
]

# one rung down the ladder for each preconditioner kind; "none" is the
# chain's floor (plain CG) and has no entry
PRECOND_DOWNGRADE = {
    "pmg": "chebyshev",
    "schwarz": "chebyshev",
    "chebyshev": "jacobi",
    "jacobi": "none",
}


@dataclasses.dataclass(frozen=True)
class SolveAttempt:
    """One rung of the fallback chain, machine-readable."""

    attempt: int
    action: str  # "initial" | "retry" | "flexible_cg"
    #            | "full_precision_precond" | "downgrade_precond:<from>-><to>"
    precond: str
    precond_dtype: str | None
    cg_variant: str
    status: str  # SolveStatus wire name, e.g. "converged"
    iterations: int
    rdotr: float

    def record(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class FallbackResult:
    """Outcome of a fallback chain: final result + the full attempt log."""

    result: Any  # the last attempt's CGResult (or dist result object)
    status: SolveStatus
    recovered: bool  # True iff the final attempt CONVERGED
    attempts: tuple[SolveAttempt, ...]

    def record(self) -> list[dict]:
        """Machine-readable log of every attempt, in order."""
        return [a.record() for a in self.attempts]


def _dtype_name(precond_dtype) -> str | None:
    return None if precond_dtype is None else np.dtype(precond_dtype).name


def _escalate(precond: str, precond_dtype, cg_variant: str):
    """Next rung as (action, precond, precond_dtype, cg_variant), or None.

    Without a preconditioner the flexible β reduces to the standard one
    (core.cg folds it), so the flexible_cg rung only applies while an M⁻¹
    is in play.
    """
    if cg_variant == "standard" and precond != "none":
        return ("flexible_cg", precond, precond_dtype, "flexible")
    if precond_dtype is not None:
        return ("full_precision_precond", precond, None, cg_variant)
    if precond in PRECOND_DOWNGRADE:
        nxt = PRECOND_DOWNGRADE[precond]
        return (f"downgrade_precond:{precond}->{nxt}", nxt, None, cg_variant)
    return None


def run_fallback_chain(
    attempt_fn: Callable[..., Any],
    *,
    precond: str = "none",
    precond_dtype=None,
    cg_variant: str = "standard",
    max_attempts: int = 7,
) -> FallbackResult:
    """Drive the escalation policy over an arbitrary solve callable.

    ``attempt_fn(precond=, precond_dtype=, cg_variant=, attempt=)`` runs
    one solve in the given configuration and returns any object exposing
    ``status`` / ``iterations`` / ``rdotr`` (a `CGResult`, or the scalars
    of a ``dist_cg`` run repackaged).  The first escalation is always one
    plain ``retry`` of the initial configuration (transient-fault
    recovery); after that the chain degrades the configuration
    (`_escalate`).  It stops at the first CONVERGED attempt, when no
    escalation remains, or after ``max_attempts`` attempts (the initial
    solve counts as attempt 0) — the default of 7 covers the longest
    possible chain (standard + fp32 + pmg: initial, retry, flexible,
    fp64, chebyshev, jacobi, plain CG).
    """
    if max_attempts < 1:
        raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")
    attempts: list[SolveAttempt] = []
    action = "initial"
    retried = False
    res = None
    status = None
    for i in range(max_attempts):
        res = attempt_fn(
            precond=precond,
            precond_dtype=precond_dtype,
            cg_variant=cg_variant,
            attempt=i,
        )
        status = SolveStatus(int(res.status))
        attempts.append(
            SolveAttempt(
                attempt=i,
                action=action,
                precond=precond,
                precond_dtype=_dtype_name(precond_dtype),
                cg_variant=cg_variant,
                status=status_name(status),
                iterations=int(res.iterations),
                rdotr=float(res.rdotr),
            )
        )
        if status == SolveStatus.CONVERGED:
            return FallbackResult(res, status, True, tuple(attempts))
        if not retried:
            action, retried = "retry", True
            continue
        nxt = _escalate(precond, precond_dtype, cg_variant)
        if nxt is None:
            break
        action, precond, precond_dtype, cg_variant = nxt
    return FallbackResult(res, status, False, tuple(attempts))


def solve_with_fallback(
    prob: PoissonProblem,
    b_g: jax.Array,
    *,
    operator: Callable[[jax.Array], jax.Array] | None = None,
    precond: str = "none",
    precond_dtype=None,
    cg_variant: str = "standard",
    tol: float = 1e-8,
    n_iter: int = 500,
    x0: jax.Array | None = None,
    max_attempts: int = 7,
    precond_kwargs: dict | None = None,
    instrument: Callable | None = None,
    **cg_kwargs,
) -> FallbackResult:
    """Assembled-path PCG with the full fallback chain behind it.

    Each attempt rebuilds the preconditioner for its rung via
    `make_preconditioner` (``precond_kwargs`` passes rung knobs such as
    ``degree`` / ``pmg_smoother`` through) and re-runs `cg_assembled` from
    the caller's ``x0``.  ``tol`` is required — a CONVERGED certificate is
    what the chain escalates toward — so ``tol=None`` (fixed-count mode)
    raises.  ``cg_kwargs`` forwards detector knobs
    (``divergence_factor`` / ``stagnation_window`` / ``stagnation_rtol``
    / ``record_history``).

    ``instrument``: optional seam called as
    ``instrument(attempt, operator, precond_apply) -> (operator,
    precond_apply)`` after the rung's preconditioner is built and before
    the solve — the fault-injection harness (`repro.testing.faults`) uses
    it to corrupt specific attempts; production callers leave it None.
    """
    if tol is None:
        raise ValueError(
            "solve_with_fallback needs tol mode: the chain escalates until "
            "a CONVERGED certificate, which fixed-count mode cannot issue"
        )
    base_op = operator if operator is not None else poisson_assembled(prob)
    pkw = dict(precond_kwargs or {})

    def attempt_fn(*, precond, precond_dtype, cg_variant, attempt) -> CGResult:
        op = base_op
        pc = None
        if precond != "none":
            pc, _info = make_preconditioner(
                precond, prob, op, precond_dtype=precond_dtype, **pkw
            )
        if instrument is not None:
            op, pc = instrument(attempt, op, pc)
        return cg_assembled(
            op,
            b_g,
            x0,
            n_iter=n_iter,
            tol=tol,
            precond=pc,
            cg_variant=cg_variant,
            **cg_kwargs,
        )

    return run_fallback_chain(
        attempt_fn,
        precond=precond,
        precond_dtype=precond_dtype,
        cg_variant=cg_variant,
        max_attempts=max_attempts,
    )
