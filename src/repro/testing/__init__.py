"""repro.testing — fault-injection and test-support utilities.

Not imported by any solver path; tests (and chaos-style soak scripts) use
these to prove the guardrails in `repro.core.cg` / `repro.core.resilience`
actually fire and recover (`repro.testing.faults`), and that the
variable-coefficient operator converges at spectral order against
manufactured solutions (`repro.testing.mms`).
"""
from .faults import (
    corrupt_wire,
    mask_precond,
    nan_at_iteration,
    negate_precond,
    on_attempt,
    skew_operator,
)
from .mms import (
    MMS_CASES,
    MMSCase,
    convergence_sweep,
    discrete_l2_error,
    exact_solution_global,
    mms_problem,
    mms_rhs,
)

__all__ = [
    "MMSCase",
    "MMS_CASES",
    "convergence_sweep",
    "discrete_l2_error",
    "exact_solution_global",
    "mms_problem",
    "mms_rhs",
    "corrupt_wire",
    "mask_precond",
    "nan_at_iteration",
    "negate_precond",
    "on_attempt",
    "skew_operator",
]
