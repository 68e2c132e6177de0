"""Fault-injection smoke: every guardrail detector fires, every fault recovers.

Drives the `repro.testing.faults` injectors through a real solve and checks
that each one trips exactly the `SolveStatus` it models, then that the
fallback chain (`repro.core.resilience`) recovers each scenario to
CONVERGED.  Exits non-zero on the first wrong verdict — CI runs this as
the fault-injection smoke leg.  A last leg checks the kernel selection
rules on whatever backend runs it: float64 is never fused on a native
backend, and an explicit request for the fused operator raises where that
kernel has no lowering instead of degrading.

    PYTHONPATH=src python examples/fault_injection.py
"""
import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (
    SolveStatus,
    build_problem,
    cg_assembled,
    poisson_assembled,
    solve_with_fallback,
    status_name,
)
from repro.core.precond import make_preconditioner
from repro.kernels import ops
from repro.testing import (
    mask_precond,
    nan_at_iteration,
    negate_precond,
    on_attempt,
    skew_operator,
)

FAILED = []


def check(name: str, got, want) -> None:
    ok = got == want
    print(f"  {'ok' if ok else 'FAIL':>4}  {name}: {got}" +
          ("" if ok else f" (wanted {want})"))
    if not ok:
        FAILED.append(name)


def main() -> int:
    enable_compile_cache()
    prob = build_problem(3, (3, 2, 2), lam=0.7, deform=0.2,
                         dtype=jnp.float64)
    a = poisson_assembled(prob)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(prob.n_global))
    pc, _ = make_preconditioner("jacobi", prob, a)

    print("detectors:")
    res = cg_assembled(a, b, n_iter=500, tol=1e-8)
    check("healthy solve", status_name(res.status), "converged")

    res = cg_assembled(a, jnp.zeros_like(b), n_iter=500, tol=1e-8)
    check("zero rhs", (status_name(res.status), int(res.iterations)),
          ("converged", 0))

    res = cg_assembled(nan_at_iteration(a, 3), b, n_iter=500, tol=1e-8)
    check("NaN in A·p at iteration 3",
          (status_name(res.status), int(res.iterations)),
          ("breakdown_nan", 3))

    res = cg_assembled(a, b, n_iter=500, tol=1e-8,
                       precond=negate_precond(pc))
    check("sign-flipped M⁻¹",
          (status_name(res.status), int(res.iterations)),
          ("breakdown_indefinite", 0))

    res = cg_assembled(skew_operator(a, 5000.0), b, n_iter=500, tol=1e-8)
    check("skew-corrupted operator", status_name(res.status), "diverged")

    res = cg_assembled(a, b, n_iter=500, tol=1e-12, cg_variant="flexible",
                       precond=mask_precond(pc, keep_every=7))
    check("rank-deficient M⁻¹", status_name(res.status), "stagnated")

    print("fallback chain:")
    fb = solve_with_fallback(
        prob, b, precond="jacobi", tol=1e-8,
        instrument=on_attempt(0, operator=lambda op: skew_operator(op, 5000.0)),
    )
    check("transient fault → retry",
          (fb.recovered, [x.action for x in fb.attempts]),
          (True, ["initial", "retry"]))

    fb = solve_with_fallback(
        prob, b, precond="jacobi", tol=1e-8,
        instrument=lambda i, op, m: (op, None if m is None
                                     else negate_precond(m)),
    )
    check("persistent M⁻¹ fault → ladder walk",
          (fb.recovered, fb.attempts[-1].precond), (True, "none"))
    for att in fb.record():
        print(f"        attempt {att['attempt']}: {att['action']:>32} "
              f"precond={att['precond']:<7} -> {att['status']}")

    print("kernel selection rules:")
    native = not ops.default_interpret()
    check("float64 never fused on a native backend",
          native and ops.should_fuse_streams(jnp.float64), False)
    check("auto policy follows HIPBONE_FUSED off a native backend",
          native or poisson_assembled(prob).fused,
          native or ops.fused_override() is True)
    try:
        poisson_assembled(prob, fused=True)
        raised = False
    except NotImplementedError:
        raised = True
    check("fused=True raises where the kernel has no lowering",
          raised, native)

    if FAILED:
        print(f"\n{len(FAILED)} scenario(s) failed: {FAILED}")
        return 1
    print("\nall fault scenarios detected and recovered")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
