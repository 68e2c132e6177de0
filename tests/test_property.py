"""Hypothesis property tests on the system's invariants.

Example budgets come from a named profile selected by the
``HYPOTHESIS_PROFILE`` env var (default ``ci``): ``fast`` for smoke runs,
``ci`` for the bounded CI budget, ``thorough`` for local fuzzing.  CI
exports ``HYPOTHESIS_PROFILE=ci`` explicitly and asserts this module is
collected (not skipped) — see .github/workflows/ci.yml.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import run_subprocess
from repro.core import (
    batched_cg_assembled,
    build_problem,
    cg_assembled,
    make_preconditioner,
    poisson_assembled,
    precond_signature,
    solver_setup_key,
)
from repro.core.gather_scatter import gather, scatter
from repro.core.mesh import build_box_mesh, partition_elements
from repro.core.operator import problem_from_mesh
from repro.comms.topology import factor3
from repro.models.moe import router_topk
from repro.models.config import ModelConfig
from repro.training.compress import dequantize_int8, quantize_int8

settings.register_profile("fast", max_examples=10, deadline=None)
settings.register_profile("ci", max_examples=25, deadline=None)
settings.register_profile("thorough", max_examples=200, deadline=None)
settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))
SMALL = settings()  # the loaded profile's budget
# Coefficient-field strategies build a full problem + preconditioner per
# example (seconds each, vs milliseconds for the pure-array properties) —
# a reduced example count keeps them inside the ci leg's wall-clock
# budget; deadline stays None profile-wide (single examples legitimately
# exceed hypothesis' default 200 ms deadline under jit compilation).
HEAVY = settings(SMALL, max_examples=max(settings().max_examples // 3, 5))


@SMALL
@given(
    n=st.integers(1, 5),
    ex=st.integers(1, 3),
    ey=st.integers(1, 3),
    ez=st.integers(1, 3),
    seed=st.integers(0, 10_000),
)
def test_gather_scatter_adjoint(n, ex, ey, ez, seed):
    """<Z x, y>_L == <x, Z^T y>_G — Z and Z^T are adjoint by construction."""
    m = build_box_mesh(n, (ex, ey, ez))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(m.n_global), jnp.float32)
    y = jnp.asarray(
        rng.standard_normal((m.n_elements, m.points_per_element)), jnp.float32
    )
    lhs = float(jnp.vdot(scatter(x, jnp.asarray(m.l2g)), y))
    rhs = float(jnp.vdot(x, gather(y, jnp.asarray(m.l2g), m.n_global)))
    assert abs(lhs - rhs) <= 1e-3 * (abs(lhs) + 1.0)


@SMALL
@given(n=st.integers(1, 4), seed=st.integers(0, 100))
def test_operator_linearity(n, seed):
    prob = build_problem(n, (2, 2, 1), lam=1.0, dtype=jnp.float32)
    a = poisson_assembled(prob)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(prob.n_global), jnp.float32)
    y = jnp.asarray(rng.standard_normal(prob.n_global), jnp.float32)
    lhs = np.array(a(2.0 * x + 3.0 * y))
    rhs = 2.0 * np.array(a(x)) + 3.0 * np.array(a(y))
    np.testing.assert_allclose(lhs, rhs, rtol=2e-4, atol=2e-4)


@SMALL
@given(p=st.integers(1, 4096))
def test_factor3_partitions_exactly(p):
    a, b, c = factor3(p)
    assert a * b * c == p and a >= b >= c >= 1


@SMALL
@given(
    px=st.integers(1, 3), py=st.integers(1, 3), pz=st.integers(1, 3),
)
def test_partition_covers_all_elements(px, py, pz):
    shape = (2 * px, 2 * py, 2 * pz)
    owner = partition_elements(shape, (px, py, pz))
    counts = np.bincount(owner, minlength=px * py * pz)
    assert (counts == counts[0]).all()  # balanced block partition
    assert counts.sum() == np.prod(shape)


@SMALL
@given(
    t=st.integers(1, 64),
    e=st.sampled_from([4, 8, 16]),
    k=st.integers(1, 4),
    seed=st.integers(0, 1000),
)
def test_router_topk_weights_normalized(t, e, k, seed):
    k = min(k, e)
    cfg = ModelConfig(
        name="x", family="moe", n_layers=1, d_model=8, n_heads=1, n_kv_heads=1,
        head_dim=8, d_ff=8, vocab_size=8, n_experts=e, experts_per_token=k,
    )
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
    w, idx, probs = router_topk(logits, cfg)
    assert w.shape == (t, k) and idx.shape == (t, k)
    np.testing.assert_allclose(np.array(w).sum(-1), 1.0, rtol=1e-5)
    assert (np.array(idx) >= 0).all() and (np.array(idx) < e).all()
    # indices unique per token
    for row in np.array(idx):
        assert len(set(row.tolist())) == k


@SMALL
@given(seed=st.integers(0, 1000), scale=st.floats(1e-3, 1e3))
def test_int8_quantization_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(256) * scale, jnp.float32)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    # error bounded by half a quantization step
    assert float(jnp.max(jnp.abs(back - x))) <= float(s) * 0.5 + 1e-6


@SMALL
@given(
    n=st.integers(2, 3),
    nb=st.integers(1, 4),
    kind=st.sampled_from(["none", "jacobi", "chebyshev"]),
    seed=st.integers(0, 1000),
)
def test_batched_solve_matches_looped(n, nb, kind, seed):
    """A (B, n_global) batched solve is iteration-for-iteration identical
    to B standalone solves — per-column independent stopping."""
    prob = build_problem(n, (2, 2, 1), lam=1.0, dtype=jnp.float32)
    a = poisson_assembled(prob)
    pc, _ = make_preconditioner(kind, prob, a)
    rng = np.random.default_rng(seed)
    b_block = jnp.asarray(
        rng.standard_normal((nb, prob.n_global)), jnp.float32
    )
    res = batched_cg_assembled(a, b_block, n_iter=150, tol=1e-5, precond=pc)
    for i in range(nb):
        ref = cg_assembled(a, b_block[i], n_iter=150, tol=1e-5, precond=pc)
        assert int(res.iterations[i]) == int(ref.iterations)
        assert int(res.status[i]) == int(ref.status)


@SMALL
@given(
    n=st.integers(2, 3),
    lam=st.floats(0.05, 10.0),
    kind=st.sampled_from(["jacobi", "chebyshev", "pmg", "schwarz"]),
    seed=st.integers(0, 1000),
)
def test_preconditioner_inverse_spd(n, lam, kind, seed):
    """M⁻¹ stays symmetric positive definite across random (N, λ, kind)
    draws — the property the PCG recurrence assumes.  Checked on the Gram
    matrix Yᵀ M⁻¹ Y of random probes: symmetry and positive eigenvalues."""
    prob = build_problem(n, (2, 1, 1), lam=lam, dtype=jnp.float32)
    a = poisson_assembled(prob)
    pc, _ = make_preconditioner(kind, prob, a)
    rng = np.random.default_rng(seed)
    y = rng.standard_normal((prob.n_global, 6)).astype(np.float32)
    mz = np.stack(
        [np.asarray(pc(jnp.asarray(y[:, j]))) for j in range(y.shape[1])],
        axis=1,
    )
    gram = y.T @ mz
    asym = np.abs(gram - gram.T).max() / (np.abs(gram).max() + 1e-12)
    assert asym < 5e-3, f"M⁻¹ not symmetric: rel asym {asym}"
    eig = np.linalg.eigvalsh(0.5 * (gram + gram.T))
    assert eig.min() > 0, f"M⁻¹ not positive definite: min eig {eig.min()}"


@SMALL
@given(
    n=st.integers(2, 3),
    lam=st.floats(0.1, 10.0),
    delta=st.floats(1e-6, 1e-2),
    kind=st.sampled_from(["none", "jacobi", "chebyshev", "pmg", "schwarz"]),
)
def test_cache_key_determinism(n, lam, delta, kind):
    """Same problem → same setup-cache key (across rebuilds); perturbing
    λ — however slightly — changes it; knob spellings canonicalize."""
    p1 = build_problem(n, (2, 1, 1), lam=lam, dtype=jnp.float32)
    p2 = build_problem(n, (2, 1, 1), lam=lam, dtype=jnp.float32)
    k1 = solver_setup_key(p1, kind)
    assert k1 == solver_setup_key(p2, kind)
    p3 = build_problem(n, (2, 1, 1), lam=lam + delta, dtype=jnp.float32)
    assert solver_setup_key(p3, kind) != k1
    # canonicalization: spelling out a default == omitting it
    assert precond_signature(kind, degree=2) == precond_signature(kind)
    assert precond_signature(kind, degree=3) != precond_signature(kind)


def _random_coefficient_problem(n, seed, bc, *, lam=0.8, dtype=jnp.float32):
    """Random positive k(x)/λ(x) fields on a 2³ box (log-normal k keeps the
    draws strictly positive with O(10×) contrast — the SPD precondition).

    Field sizes are bounded by n ≤ 3 on 8 elements so the whole strategy
    stays far inside the hypothesis ``ci`` example budget.
    """
    m = build_box_mesh(n, (2, 2, 2))
    rng = np.random.default_rng(seed)
    shape = m.coords.shape[:2]
    k = np.exp(rng.normal(0.0, 0.8, shape))
    lam_field = 0.05 + np.abs(rng.normal(lam, 0.5, shape))
    return problem_from_mesh(
        m, lam=lam, dtype=dtype, k=k, lam_field=lam_field, bc=bc
    )


def _masked_probes(prob, seed, cols=6):
    """Random probe block restricted to the Dirichlet-interior subspace."""
    rng = np.random.default_rng(seed)
    y = rng.standard_normal((prob.n_global, cols)).astype(np.float32)
    if prob.mask is not None:
        y = y * np.asarray(prob.mask, np.float32)[:, None]
    return y


def _assert_gram_spd(y, apply, label):
    mz = np.stack(
        [np.asarray(apply(jnp.asarray(y[:, j]))) for j in range(y.shape[1])],
        axis=1,
    )
    gram = y.T @ mz
    asym = np.abs(gram - gram.T).max() / (np.abs(gram).max() + 1e-12)
    assert asym < 5e-3, f"{label} not symmetric: rel asym {asym}"
    eig = np.linalg.eigvalsh(0.5 * (gram + gram.T))
    assert eig.min() > 0, f"{label} not positive definite: min eig {eig.min()}"


@HEAVY
@given(
    n=st.integers(2, 3),
    seed=st.integers(0, 1000),
    bc=st.sampled_from([None, "dirichlet", "mixed", "neumann"]),
)
def test_operator_spd_variable_coefficients(n, seed, bc):
    """A = -∇·(k∇) + λ(x) stays SPD on the Dirichlet-interior subspace for
    random positive coefficient draws — the property CG itself assumes."""
    prob = _random_coefficient_problem(n, seed, bc)
    _assert_gram_spd(
        _masked_probes(prob, seed + 1), poisson_assembled(prob), "A"
    )


@HEAVY
@given(
    n=st.integers(2, 3),
    seed=st.integers(0, 1000),
    kind=st.sampled_from(["jacobi", "chebyshev", "pmg", "schwarz"]),
    bc=st.sampled_from([None, "mixed"]),
)
def test_ladder_spd_variable_coefficients(n, seed, kind, bc):
    """Every preconditioner rung's M⁻¹ stays SPD under random coefficient
    fields and bc masks (pmg exercises the field-resampling coarsen path,
    schwarz the element-mean FDM blocks)."""
    prob = _random_coefficient_problem(n, seed, bc)
    a = poisson_assembled(prob)
    pc, _ = make_preconditioner(kind, prob, a)
    _assert_gram_spd(_masked_probes(prob, seed + 1), pc, f"M⁻¹[{kind}]")


@HEAVY
@given(
    n=st.integers(2, 3),
    seed=st.integers(0, 1000),
    kind=st.sampled_from(["none", "jacobi", "pmg"]),
)
def test_cache_key_coefficient_sensitivity(n, seed, kind):
    """The setup-cache key misses whenever the physics changes — and ONLY
    then: legacy constant-λ keys are unchanged by the coefficient
    extension, rebuilding the same fields hits, perturbing one node of k,
    swapping the family, or flipping a bc tag all miss."""
    legacy = build_problem(n, (2, 2, 2), lam=0.8, dtype=jnp.float32)
    const = build_problem(
        n, (2, 2, 2), lam=0.8, dtype=jnp.float32, coefficient="const"
    )
    assert solver_setup_key(legacy, kind) == solver_setup_key(const, kind)

    p1 = _random_coefficient_problem(n, seed, "mixed")
    p2 = _random_coefficient_problem(n, seed, "mixed")
    k1 = solver_setup_key(p1, kind)
    assert k1 == solver_setup_key(p2, kind)          # determinism → hit
    assert k1 != solver_setup_key(legacy, kind)      # physics differs

    # one node, one ulp-scale (in the stored fp32 dtype) perturbation —
    # the key hashes the fields as the problem stores them, so the nudge
    # must survive the dtype cast
    k_pert = np.asarray(p1.k, np.float64).copy()
    k_pert.flat[seed % k_pert.size] *= 1.0 + 1e-6
    p3 = problem_from_mesh(
        p1.mesh, lam=p1.lam, dtype=jnp.float32, k=k_pert,
        lam_field=np.asarray(p1.lam_field, np.float64), bc="mixed",
    )
    assert solver_setup_key(p3, kind) != k1          # any field bit → miss

    p4 = _random_coefficient_problem(n, seed, "dirichlet")
    assert solver_setup_key(p4, kind) != k1          # bc tag → miss

    smooth = build_problem(
        n, (2, 2, 2), lam=0.8, dtype=jnp.float32, coefficient="smooth"
    )
    checker = build_problem(
        n, (2, 2, 2), lam=0.8, dtype=jnp.float32, coefficient="checker"
    )
    assert solver_setup_key(smooth, kind) != solver_setup_key(checker, kind)


@pytest.mark.slow
def test_sharded_parity_random_coefficient_fields():
    """Sharded-vs-single parity holds under random positive coefficient
    draws, not just the named families — three seeded draws through the
    full dist_cg stack on 8 fake devices.

    The two solves sum their dots in different orders, so their ‖r‖²
    histories agree to rounding over the first 20 iterations (a fault in
    the sharded operator, masks or screen breaks that at once) and may part
    later by CG's amplification of rounding (seed 0: 5e-15 relative up to
    iteration 24, 4e-9 at iteration 29); the tol=1e-10 crossing may then
    land one iteration apart (seed 42: 120 sharded against 121
    single-device, the solutions 2.2e-10 apart)."""
    run_subprocess(
        """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.compat import make_mesh
from repro.comms.topology import ProcessGrid
from repro.core import build_box_mesh, cg_assembled, poisson_assembled
from repro.core.operator import problem_from_mesh
from repro.core.distributed import build_dist_problem, dist_cg, _ordered_elements

N = 3
grid = ProcessGrid((2, 2, 2)); local = (1, 1, 1); shape = (2, 2, 2)
mesh = make_mesh((8,), ("ranks",))
GX, GY = shape[0] * N + 1, shape[1] * N + 1
ordered, _ = _ordered_elements(local)


def partition_field(field):
    out = np.zeros((grid.size, len(ordered)) + field.shape[1:])
    for r in range(grid.size):
        ci, cj, ck = grid.coords(r)
        ex = ordered[:, 0] + ci * local[0]
        ey = ordered[:, 1] + cj * local[1]
        ez = ordered[:, 2] + ck * local[2]
        out[r] = field[ex + shape[0] * (ey + shape[1] * ez)]
    return out


def boxes_from_global(prob, vec):
    mx, my, mz = prob.box_shape
    out = np.zeros((grid.size, prob.m3))
    for r in range(grid.size):
        ci, cj, ck = grid.coords(r)
        ox, oy, oz = ci * local[0] * N, cj * local[1] * N, ck * local[2] * N
        x, y, z = np.meshgrid(
            np.arange(mx), np.arange(my), np.arange(mz), indexing="ij"
        )
        gidx = (ox + x) + GX * ((oy + y) + GY * (oz + z))
        out[r] = vec[gidx.transpose(2, 1, 0).reshape(-1)]
    return out


for seed in (0, 7, 42):
    rng = np.random.default_rng(seed)
    m = build_box_mesh(N, shape)
    fshape = m.coords.shape[:2]
    k = np.exp(rng.normal(0.0, 0.8, fshape))
    lam_field = 0.05 + np.abs(rng.normal(0.8, 0.5, fshape))
    ref = problem_from_mesh(
        m, lam=0.8, dtype=jnp.float64, k=k, lam_field=lam_field, bc="mixed"
    )
    bg = rng.standard_normal(ref.n_global) * np.asarray(ref.mask, np.float64)
    res = cg_assembled(
        poisson_assembled(ref), jnp.asarray(bg), n_iter=300, tol=1e-10,
        record_history=True,
    )
    prob = build_dist_problem(
        N, grid, local, lam=0.8, dtype=jnp.float64,
        k=partition_field(k), lam_field=partition_field(lam_field),
        bc="mixed",
    )
    run = jax.jit(dist_cg(prob, mesh, jnp.asarray(boxes_from_global(prob, bg)),
                          n_iter=300, tol=1e-10, record_history=True))
    x_boxes, rdotr, iters, status, hist = run()
    err = np.abs(
        np.asarray(x_boxes) - boxes_from_global(prob, np.asarray(res.x))
    ).max()
    h_dist = np.asarray(hist)[:20]
    h_one = np.asarray(res.rdotr_history)[:20]
    hist_err = np.max(np.abs(h_dist - h_one) / np.abs(h_one))
    print(seed, int(iters), int(res.iterations), err, hist_err)
    assert int(status) == 0 and int(res.status) == 0, (seed, int(status), int(res.status))
    assert abs(int(iters) - int(res.iterations)) <= 1, (seed, int(iters), int(res.iterations))
    assert hist_err <= 1e-12, (seed, hist_err)
    assert err < 1e-8, (seed, err)
print("PARITY-OK")
""",
        timeout=900,
    )


@SMALL
@given(n=st.integers(1, 8), seed=st.integers(0, 50))
def test_ssd_chunk_invariance(n, seed):
    """Chunk size must not change SSD results (associativity of the scan)."""
    from repro.models.mamba2 import ssd_chunked

    s = 8 * n
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((1, s, 2, 4)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((1, s, 2))) * 0.3 + 0.05, jnp.float32)
    a = jnp.asarray(-np.abs(rng.standard_normal(2)) - 0.1, jnp.float32)
    bm = jnp.asarray(rng.standard_normal((1, s, 1, 3)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((1, s, 1, 3)), jnp.float32)
    y1, s1 = ssd_chunked(x, dt, a, bm, cm, chunk=8)
    y2, s2 = ssd_chunked(x, dt, a, bm, cm, chunk=min(s, 4 * n))
    np.testing.assert_allclose(np.array(y1), np.array(y2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.array(s1), np.array(s2), rtol=2e-4, atol=2e-4)
