"""Single-pass fused assembled operator vs the split pipeline.

The acceptance bar for kernels/poisson_fused.py: the fused kernel matches
``poisson_assembled`` to fp64 round-off (<= 1e-12 rel) across degrees and
deformed coordinates, PCG iteration counts are identical with the fused
operator swapped in, and the auto-enable policy (``should_fuse_operator``
+ the HIPBONE_FUSED override) picks the right path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.core import build_problem, cg_assembled, poisson_assembled  # noqa: E402
from repro.core.precond import make_preconditioner  # noqa: E402
from repro.kernels import ops  # noqa: E402

SHAPES = {3: (3, 2, 2), 7: (2, 2, 2), 9: (2, 2, 2), 15: (2, 2, 2)}


def _rand_x(prob, rng, dtype):
    return jnp.asarray(rng.standard_normal(prob.n_global), dtype)


@pytest.mark.parametrize("n", [3, 7, 9, 15])
def test_fused_matches_split_fp64(n, rng):
    prob = build_problem(n, SHAPES[n], lam=1.3, deform=0.15, dtype=jnp.float64)
    x = _rand_x(prob, rng, jnp.float64)
    want = poisson_assembled(prob, fused=False)(x)
    got = ops.poisson_assembled_fused(
        x, prob.l2g, prob.g, prob.w_local, prob.d, lam=prob.lam, interpret=True
    )
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel <= 1e-12


@pytest.mark.parametrize("coefficient", ["smooth", "checker"])
@pytest.mark.parametrize("deform", [0.0, 0.15])
def test_fused_matches_split_variable_coefficient_fp64(coefficient, deform, rng):
    """The fused kernel sees k(x)/λ(x)/bc only through its g/w streams and
    the mask wrap — parity with the split pipeline must stay at fp64
    round-off, deformed coordinates included."""
    prob = build_problem(
        3, (2, 2, 2), lam=0.7, deform=deform, dtype=jnp.float64,
        coefficient=coefficient, bc="mixed",
    )
    x = _rand_x(prob, rng, jnp.float64)
    want = poisson_assembled(prob, fused=False)(x)
    got = poisson_assembled(
        prob, fused=True, fused_kwargs={"interpret": True}
    )(x)
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel <= 1e-12


def test_fused_matches_split_fp32(rng):
    prob = build_problem(5, (2, 2, 2), lam=0.9, deform=0.12, dtype=jnp.float32)
    x = _rand_x(prob, rng, jnp.float32)
    want = poisson_assembled(prob, fused=False)(x)
    got = ops.poisson_assembled_fused(
        x, prob.l2g, prob.g, prob.w_local, prob.d, lam=prob.lam, interpret=True
    )
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel < 3e-6


@pytest.mark.parametrize("block_e", [1, 2, 4, 8])
def test_fused_block_sweep_and_padding(block_e, rng):
    # E=12 is not a multiple of 8; N_G is far from a 128-lane multiple
    prob = build_problem(3, (3, 2, 2), lam=0.7, deform=0.1, dtype=jnp.float64)
    x = _rand_x(prob, rng, jnp.float64)
    want = poisson_assembled(prob, fused=False)(x)
    got = ops.poisson_assembled_fused(
        x,
        prob.l2g,
        prob.g,
        prob.w_local,
        prob.d,
        lam=prob.lam,
        block_e=block_e,
        interpret=True,
    )
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel <= 1e-12


def test_fused_gather_mode_loop(rng):
    """The PrefetchScalarGridSpec dynamic-slice fallback matches too."""
    prob = build_problem(3, (2, 2, 1), lam=1.0, deform=0.1, dtype=jnp.float64)
    x = _rand_x(prob, rng, jnp.float64)
    want = poisson_assembled(prob, fused=False)(x)
    got = ops.poisson_assembled_fused(
        x,
        prob.l2g,
        prob.g,
        prob.w_local,
        prob.d,
        lam=prob.lam,
        interpret=True,
        gather_mode="loop",
    )
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel <= 1e-12


@pytest.mark.parametrize("n", [3, 7])
def test_pcg_iterations_identical_with_fused_operator(n, rng):
    prob = build_problem(n, SHAPES[n], lam=0.5, deform=0.15, dtype=jnp.float64)
    b = _rand_x(prob, rng, jnp.float64)
    a_split = poisson_assembled(prob, fused=False)
    a_fused = poisson_assembled(
        prob, fused=True, fused_kwargs={"interpret": True}
    )
    pc, _ = make_preconditioner("jacobi", prob, a_split)
    res_s = cg_assembled(a_split, b, n_iter=300, tol=1e-8, precond=pc)
    res_f = cg_assembled(a_fused, b, n_iter=300, tol=1e-8, precond=pc)
    assert int(res_s.iterations) == int(res_f.iterations)
    rel = float(
        jnp.max(jnp.abs(res_f.x - res_s.x)) / jnp.max(jnp.abs(res_s.x))
    )
    assert rel < 1e-10


def test_should_fuse_operator_policy(monkeypatch):
    monkeypatch.delenv("HIPBONE_FUSED", raising=False)
    # CPU backend -> interpret mode -> auto policy stays off
    assert ops.default_interpret()
    assert not ops.should_fuse_operator()
    monkeypatch.setenv("HIPBONE_FUSED", "1")
    assert ops.should_fuse_operator()
    assert ops.should_fuse_streams(jnp.float64)
    monkeypatch.setenv("HIPBONE_FUSED", "0")
    assert not ops.should_fuse_operator()
    assert not ops.should_fuse_streams(jnp.float32)


def test_poisson_assembled_switch(monkeypatch, rng):
    prob = build_problem(3, (2, 2, 2), lam=1.0, dtype=jnp.float64)
    monkeypatch.delenv("HIPBONE_FUSED", raising=False)
    assert poisson_assembled(prob).fused is False
    monkeypatch.setenv("HIPBONE_FUSED", "1")
    ap = poisson_assembled(prob)
    assert ap.fused is True
    x = _rand_x(prob, rng, jnp.float64)
    want = poisson_assembled(prob, fused=False)(x)
    rel = float(jnp.max(jnp.abs(ap(x) - want)) / jnp.max(jnp.abs(want)))
    assert rel <= 1e-12
    # an explicit local_op pins the split pipeline even under the override
    calls = []

    def counting_op(u, g, d, lam, w, jw=None):
        calls.append(1)
        from repro.core.operator import local_poisson

        return local_poisson(u, g, d, lam, w, jw)

    a_custom = poisson_assembled(prob, local_op=counting_op)
    assert a_custom.fused is False
    a_custom(x)
    assert calls
    with pytest.raises(ValueError):
        poisson_assembled(prob, local_op=counting_op, fused=True)


def test_fused_vmem_budget_helpers():
    from repro.kernels.poisson_fused import fused_vmem_bytes, pick_fused_block_e

    eb = pick_fused_block_e(7, 100_000, jnp.float32)
    n_pad = -(-100_000 // 128) * 128
    assert fused_vmem_bytes(eb, 8, n_pad, jnp.float32) <= 8 * 2**20
    assert eb >= 1
