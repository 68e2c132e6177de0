"""Solver guardrails: detectors × injected faults, fallback-chain recovery.

Every `repro.testing.faults` injector is driven into the solve it targets
and must trip exactly the `SolveStatus` its docstring promises; the
fallback chain (`core.resilience`) must then recover each scenario to
CONVERGED.  The slow 8-rank test corrupts one rank's wire payloads and
asserts every replica exits on the same iteration with the same status —
the lockstep guarantee that makes the detectors safe under shard_map.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import (
    SolveStatus,
    build_problem,
    cg_assembled,
    cg_scattered,
    poisson_assembled,
    run_fallback_chain,
    solve_with_fallback,
    status_name,
)
from repro.core.operator import poisson_scattered
from repro.core.precond import make_preconditioner
from repro.testing import (
    mask_precond,
    nan_at_iteration,
    negate_precond,
    on_attempt,
    skew_operator,
)


@pytest.fixture(scope="module")
def prob64():
    jax.config.update("jax_enable_x64", True)
    return build_problem(3, (3, 2, 2), lam=0.7, deform=0.2, dtype=jnp.float64)


@pytest.fixture(scope="module")
def rhs(prob64):
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.standard_normal(prob64.n_global))


# ---------------------------------------------------------------- detectors


def test_healthy_solve_converges_with_detectors_on(prob64, rhs):
    """Guardrails must be free on the healthy path: same iteration count
    with every detector armed as with all of them disabled."""
    a = poisson_assembled(prob64)
    res = cg_assembled(a, rhs, n_iter=500, tol=1e-8)
    off = cg_assembled(a, rhs, n_iter=500, tol=1e-8,
                       divergence_factor=None, stagnation_window=None)
    assert res.status == SolveStatus.CONVERGED
    assert status_name(res.status) == "converged"
    assert int(res.iterations) == int(off.iterations)


def test_zero_rhs_converged_at_zero_iterations(prob64):
    """Satellite: rdotr0 = 0 reports CONVERGED/0 in both iteration modes."""
    a = poisson_assembled(prob64)
    zero = jnp.zeros(prob64.n_global, jnp.float64)
    for kwargs in ({"tol": 1e-8}, {}):  # tol mode and fixed-count mode
        res = cg_assembled(a, zero, n_iter=50, **kwargs)
        assert res.status == SolveStatus.CONVERGED, kwargs
        assert int(res.iterations) == 0, kwargs
        assert np.array_equal(np.array(res.x), np.zeros(prob64.n_global))


def test_zero_rhs_scattered(prob64):
    op = poisson_scattered(prob64)
    zero = jnp.zeros((prob64.mesh.n_elements,
                      prob64.mesh.points_per_element), jnp.float64)
    res = cg_scattered(op, zero, prob64.w_local, n_iter=50, tol=1e-8)
    assert res.status == SolveStatus.CONVERGED
    assert int(res.iterations) == 0


def test_nan_at_iteration_k_detected(prob64, rhs):
    """NaN injected into A·p at iteration k exits AT iteration k."""
    a = poisson_assembled(prob64)
    res = cg_assembled(nan_at_iteration(a, 3), rhs, n_iter=500, tol=1e-8)
    assert res.status == SolveStatus.BREAKDOWN_NAN
    assert int(res.iterations) == 3


def test_nan_in_initial_residual(prob64, rhs):
    """Call 0 is A·x₀: a NaN there is caught before the loop starts."""
    a = poisson_assembled(prob64)
    res = cg_assembled(nan_at_iteration(a, 0), rhs, n_iter=500, tol=1e-8)
    assert res.status == SolveStatus.BREAKDOWN_NAN
    assert int(res.iterations) == 0


def test_nan_recorded_in_fixed_count_mode(prob64, rhs):
    """lax.scan cannot exit early; the first failure is still recorded."""
    a = poisson_assembled(prob64)
    res = cg_assembled(nan_at_iteration(a, 5), rhs, n_iter=30)
    assert res.status == SolveStatus.BREAKDOWN_NAN
    assert int(res.iterations) == 30  # fixed-count always runs the budget


def test_indefinite_operator_detected(prob64, rhs):
    a = poisson_assembled(prob64)
    res = cg_assembled(lambda x: -a(x), rhs, n_iter=500, tol=1e-8)
    assert res.status == SolveStatus.BREAKDOWN_INDEFINITE
    assert int(res.iterations) <= 1


def test_sign_flipped_precond_detected(prob64, rhs):
    """−M⁻¹ shows up as r·z < 0 in the very first application (p·Ap stays
    positive — A is untouched), caught before iteration 1."""
    a = poisson_assembled(prob64)
    pc, _ = make_preconditioner("jacobi", prob64, a)
    res = cg_assembled(a, rhs, n_iter=500, tol=1e-8,
                       precond=negate_precond(pc))
    assert res.status == SolveStatus.BREAKDOWN_INDEFINITE
    assert int(res.iterations) == 0


def test_skew_corruption_diverges(prob64, rhs):
    """Skew-symmetric corruption keeps p·Ap > 0 (no indefinite trip) but
    blows up the recurrence: the DIVERGED detector's canonical trigger."""
    a = poisson_assembled(prob64)
    res = cg_assembled(skew_operator(a, 5000.0), rhs, n_iter=500, tol=1e-8)
    assert res.status == SolveStatus.DIVERGED, status_name(res.status)
    assert int(res.iterations) < 500


def test_rank_deficient_precond_stagnates(prob64, rhs):
    """A partially-zeroed (PSD, rank-deficient) M⁻¹ pins the residual at a
    floor: STAGNATED after the no-progress window."""
    a = poisson_assembled(prob64)
    pc, _ = make_preconditioner("jacobi", prob64, a)
    res = cg_assembled(a, rhs, n_iter=500, tol=1e-12,
                       precond=mask_precond(pc, keep_every=7),
                       cg_variant="flexible")
    assert res.status == SolveStatus.STAGNATED, status_name(res.status)
    assert int(res.iterations) >= 50  # needs a full window to decide


def test_detectors_can_be_disabled(prob64, rhs):
    """divergence_factor=None / stagnation_window=None fall back to the
    pre-guardrail behaviour: the budget runs out as MAX_ITER."""
    a = poisson_assembled(prob64)
    res = cg_assembled(skew_operator(a, 5000.0), rhs, n_iter=60, tol=1e-8,
                       divergence_factor=None, stagnation_window=None)
    assert res.status == SolveStatus.MAX_ITER
    assert int(res.iterations) == 60


def test_status_under_jit_with_history(prob64, rhs):
    a = poisson_assembled(prob64)
    run = jax.jit(lambda bb: cg_assembled(
        a, bb, n_iter=500, tol=1e-8, record_history=True))
    res = run(rhs)
    assert int(res.status) == SolveStatus.CONVERGED
    hist = np.asarray(res.rdotr_history)[: int(res.iterations)]
    assert hist[-1] < hist[0]


def test_status_name_roundtrip():
    for s in SolveStatus:
        assert status_name(s) == s.name.lower()
    with pytest.raises(ValueError):
        status_name(99)


# ---------------------------------------------------------- fallback chain


def test_fallback_healthy_single_attempt(prob64, rhs):
    fb = solve_with_fallback(prob64, rhs, precond="jacobi", tol=1e-8)
    assert fb.recovered and fb.status == SolveStatus.CONVERGED
    assert [a.action for a in fb.attempts] == ["initial"]


def test_fallback_retry_recovers_transient_fault(prob64, rhs):
    """A one-shot skew corruption on attempt 0 is outrun by the retry rung
    — no configuration degradation needed."""
    fb = solve_with_fallback(
        prob64, rhs, precond="jacobi", tol=1e-8,
        instrument=on_attempt(0, operator=lambda op: skew_operator(op, 5000.0)),
    )
    assert fb.recovered
    assert [(a.action, a.status) for a in fb.attempts] == [
        ("initial", "diverged"),
        ("retry", "converged"),
    ]
    # the recovery attempt kept the caller's configuration
    assert fb.attempts[-1].precond == "jacobi"


def test_fallback_retry_recovers_transient_nan(prob64, rhs):
    """nan_at_iteration's counter keeps advancing across attempts, so the
    retry of the *same wrapped operator* runs clean — SDC semantics."""
    base = poisson_assembled(prob64)
    faulty = nan_at_iteration(base, 2)
    fb = solve_with_fallback(prob64, rhs, operator=faulty,
                             precond="jacobi", tol=1e-8)
    assert fb.recovered
    assert fb.attempts[0].status == "breakdown_nan"
    assert fb.attempts[1].action == "retry"


def test_fallback_walks_ladder_on_persistent_precond_fault(prob64, rhs):
    """A *persistent* sign-flipped M⁻¹ defeats retry and flexible β; the
    chain keeps degrading until plain CG (no M⁻¹ to corrupt) converges."""

    def instrument(i, op, pc):
        return op, (None if pc is None else negate_precond(pc))

    fb = solve_with_fallback(prob64, rhs, precond="jacobi", tol=1e-8,
                             instrument=instrument)
    assert fb.recovered
    assert [a.action for a in fb.attempts] == [
        "initial", "retry", "flexible_cg", "downgrade_precond:jacobi->none",
    ]
    assert fb.attempts[-1].precond == "none"
    assert all(a.status == "breakdown_indefinite" for a in fb.attempts[:-1])
    # the attempt log is json-ready
    rec = fb.record()
    assert rec[-1]["status"] == "converged"
    assert {type(v) for r in rec for v in r.values()} <= {
        str, int, float, type(None)
    }


def test_fallback_chain_exhaustion():
    """attempt_fn that never converges: the chain stops after walking every
    rung and reports recovered=False with the full log."""

    class Fail:
        status = int(SolveStatus.STAGNATED)
        iterations = 7
        rdotr = 1.0

    calls = []

    def attempt_fn(**kw):
        calls.append((kw["precond"], kw["precond_dtype"], kw["cg_variant"]))
        return Fail()

    fb = run_fallback_chain(attempt_fn, precond="pmg",
                            precond_dtype="float32", cg_variant="standard")
    assert not fb.recovered and fb.status == SolveStatus.STAGNATED
    assert [a.action for a in fb.attempts] == [
        "initial", "retry", "flexible_cg", "full_precision_precond",
        "downgrade_precond:pmg->chebyshev",
        "downgrade_precond:chebyshev->jacobi",
        "downgrade_precond:jacobi->none",
    ]
    # the last rung really is plain CG
    assert calls[-1] == ("none", None, "flexible")


def test_fallback_max_attempts_cap():
    class Fail:
        status = int(SolveStatus.DIVERGED)
        iterations = 1
        rdotr = float("inf")

    fb = run_fallback_chain(lambda **kw: Fail(), precond="pmg",
                            max_attempts=2)
    assert not fb.recovered and len(fb.attempts) == 2
    with pytest.raises(ValueError):
        run_fallback_chain(lambda **kw: Fail(), max_attempts=0)


def test_fallback_requires_tol(prob64, rhs):
    with pytest.raises(ValueError, match="tol"):
        solve_with_fallback(prob64, rhs, tol=None)


# -------------------------------------------------------- config hardening


def test_config_rejects_invalid_knob_combos():
    """Satellite: PoissonConfig fails fast with the offending knob named
    instead of surfacing as a deep-stack solver failure."""
    from repro.configs.hipbone import PoissonConfig

    base = dict(name="bad", n_degree=7, local_elems=(2, 2, 2))
    cases = [
        dict(n_degree=0), dict(local_elems=(0, 2, 2)), dict(lam=0.0),
        dict(n_iter=0), dict(tol=-1.0), dict(dtype="float16"),
        dict(precond="ilu"), dict(cheb_degree=0),
        dict(n_degree=1, precond="pmg"), dict(schwarz_overlap=7),
        dict(precond_dtype="bfloat16"),
        dict(precond_dtype="float32", precond="none"),
        dict(cg_variant="cgs"),
        dict(divergence_factor=1.0), dict(stagnation_window=0),
        dict(stagnation_rtol=0.0),
    ]
    for kw in cases:
        args = dict(base)
        args.update(kw)
        with pytest.raises(ValueError, match="bad"):
            PoissonConfig(**args)


def test_config_warns_on_narrowed_precond_with_standard_beta():
    """Satellite: the documented legal-but-suspect combination — fp32 M⁻¹
    with the Fletcher–Reeves β — emits ConfigWarning, and the flexible-β
    pairing stays silent."""
    from repro.configs.hipbone import ConfigWarning, PoissonConfig

    with pytest.warns(ConfigWarning, match="flexible"):
        PoissonConfig("w", 7, (2, 2, 2), precond="jacobi",
                      dtype="float64", precond_dtype="float32")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        PoissonConfig("ok", 7, (2, 2, 2), precond="jacobi",
                      dtype="float64", precond_dtype="float32",
                      cg_variant="flexible")


def test_config_detector_defaults_mirror_cg():
    """The config's detector-knob defaults must stay in sync with the
    solver's module constants (both are documented contracts)."""
    from repro.configs.hipbone import CONFIGS, REDUCED
    from repro.core import cg

    assert REDUCED.divergence_factor == cg.DIVERGENCE_FACTOR
    assert REDUCED.stagnation_window == cg.STAGNATION_WINDOW
    assert REDUCED.stagnation_rtol == cg.STAGNATION_RTOL
    # every shipped preset passes its own validation at import time, and
    # the shipped mixed-precision presets pair fp32 chains with flexible β
    for cfg in CONFIGS.values():
        if cfg.precond_dtype is not None and cfg.precond_dtype != cfg.dtype:
            assert cfg.cg_variant == "flexible", cfg.name


# ------------------------------------------------ native kernel selection


@pytest.fixture
def native_backend(monkeypatch):
    """Make the kernel policies see a TPU backend (Pallas compiles natively).

    Only the policies and the kernels' interpret resolution are exercised:
    they raise or answer before anything is lowered.
    """
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from repro.kernels import ops

    assert not ops.default_interpret()


@pytest.mark.parametrize("override", [None, "1", "0"])
def test_native_backend_never_fuses_float64(
    native_backend, prob64, override, monkeypatch
):
    """No Pallas kernel is selected for float64 on a native backend,
    whatever HIPBONE_FUSED says, and a direct call refuses float64."""
    from repro.kernels import ops

    if override is None:
        monkeypatch.delenv("HIPBONE_FUSED", raising=False)
    else:
        monkeypatch.setenv("HIPBONE_FUSED", override)
    assert ops.should_fuse_streams(jnp.float64) is False
    if override != "1":
        assert ops.should_fuse_operator() is False
        assert poisson_assembled(prob64).fused is False
    from repro.kernels.backend import resolve_interpret

    # the check every *_pallas entry point makes while it is traced
    with pytest.raises(TypeError, match="float64"):
        resolve_interpret(None, jnp.float32, jnp.float64, kernel="any")
    assert resolve_interpret(True, jnp.float64, kernel="any") is True


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_explicit_fused_operator_raises_on_native_backend(
    native_backend, dtype, monkeypatch
):
    """The fused operator has no native lowering: fused=True and
    HIPBONE_FUSED=1 raise instead of degrading to the split pipeline."""
    prob = build_problem(3, (2, 2, 2), lam=0.7, dtype=jnp.dtype(dtype))
    monkeypatch.delenv("HIPBONE_FUSED", raising=False)
    assert poisson_assembled(prob).fused is False
    with pytest.raises(NotImplementedError, match="no native Pallas lowering"):
        poisson_assembled(prob, fused=True)
    monkeypatch.setenv("HIPBONE_FUSED", "1")
    with pytest.raises(NotImplementedError, match="no native Pallas lowering"):
        poisson_assembled(prob)


# ----------------------------------------------------------- sharded paths


@pytest.mark.slow
def test_corrupted_wire_exits_all_ranks_in_lockstep():
    """ISSUE acceptance: corrupt ONE rank's outgoing halo payloads on an
    8-rank solve — every rank must exit on the same iteration with the
    same status (detector inputs are psum-derived), and the same solve
    runs clean without the hook."""
    run_subprocess(
        """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core.distributed import build_dist_problem, dist_cg
from repro.comms.topology import ProcessGrid
from repro.core.cg import SolveStatus
from repro.testing import corrupt_wire

N = 3
grid = ProcessGrid((2, 2, 2)); local = (2, 1, 1)
mesh = make_mesh((8,), ("ranks",))
prob = build_dist_problem(N, grid, local, lam=0.8, dtype=jnp.float64)
rng = np.random.default_rng(0)
b = jnp.asarray(rng.standard_normal((8, prob.m3)))

# healthy baseline, per-rank observability
run = jax.jit(dist_cg(prob, mesh, b, n_iter=200, tol=1e-10,
                      precond="jacobi", per_rank_stats=True))
x, rdotr, iters, status, hist = run()
iters, status = np.asarray(iters), np.asarray(status)
assert iters.shape == (8,) and status.shape == (8,)
assert np.unique(status).size == 1 and status[0] == SolveStatus.CONVERGED
healthy_iters = int(iters[0])
assert np.unique(iters).size == 1 and healthy_iters < 200

# rank 3 poisons every payload it sends; install BEFORE tracing
with corrupt_wire(3, mode="nan"):
    bad = jax.jit(dist_cg(prob, mesh, b, n_iter=200, tol=1e-10,
                          precond="jacobi", per_rank_stats=True))
    xb, rdb, itb, stb, _ = bad()
itb, stb = np.asarray(itb), np.asarray(stb)
assert np.unique(stb).size == 1, stb
assert stb[0] == SolveStatus.BREAKDOWN_NAN, stb
assert np.unique(itb).size == 1, itb
assert int(itb[0]) <= 1, itb  # NaN spreads through the first halo sum

# zeroed payloads corrupt the operator less dramatically: still a
# single lockstep non-CONVERGED exit on every rank
with corrupt_wire(3, mode="zero"):
    z = jax.jit(dist_cg(prob, mesh, b, n_iter=200, tol=1e-10,
                        precond="jacobi", per_rank_stats=True))
    _, _, itz, stz, _ = z()
itz, stz = np.asarray(itz), np.asarray(stz)
assert np.unique(stz).size == 1 and np.unique(itz).size == 1, (stz, itz)
assert stz[0] != SolveStatus.CONVERGED, stz

# hook gone after the context: clean solve again, same iteration count
again = jax.jit(dist_cg(prob, mesh, b, n_iter=200, tol=1e-10,
                        precond="jacobi"))
_, _, it2, st2, _ = again()
assert int(st2) == SolveStatus.CONVERGED and int(it2) == healthy_iters

# zero-RHS edge case, sharded: CONVERGED at 0 iterations
zrun = jax.jit(dist_cg(prob, mesh, jnp.zeros_like(b), n_iter=200,
                       tol=1e-10))
_, _, it0, st0, _ = zrun()
assert int(st0) == SolveStatus.CONVERGED and int(it0) == 0
print("OK", healthy_iters)
""",
        devices=8,
    )


def test_dist_status_in_fixed_count_mode():
    """Fixed-count sharded solve (the scan path check_rep relies on) still
    threads a status: MAX_ITER on completion."""
    run_subprocess(
        """
import jax
import numpy as np, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core.distributed import build_dist_problem, dist_cg
from repro.comms.topology import ProcessGrid
from repro.core.cg import SolveStatus

grid = ProcessGrid((2, 1, 1))
mesh = make_mesh((2,), ("ranks",))
prob = build_dist_problem(3, grid, (1, 1, 1), lam=1.0, dtype=jnp.float32)
b = jnp.asarray(
    np.random.default_rng(0).standard_normal((2, prob.m3)), jnp.float32)
x, rdotr, iters, status, hist = jax.jit(
    dist_cg(prob, mesh, b, n_iter=20))()
assert int(status) == SolveStatus.MAX_ITER and int(iters) == 20
print("OK")
""",
        devices=2,
    )
