"""Materialized Galerkin coarse operators: block assembly, V-cycle wiring,
apply-count accounting, Pallas matvec, sharded parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import build_problem, cg_assembled, poisson_assembled
from repro.core.galerkin import (
    coarsen_element_blocks,
    galerkin_assembled_diagonal,
    galerkin_block_apply,
    galerkin_element_blocks,
    galerkin_ladder_blocks,
)
from repro.core.gather_scatter import assembly
from repro.core.operator import coarsen_problem, local_operator_columns
from repro.core.precond import (
    make_pmg_preconditioner,
    make_preconditioner,
    make_transfer_pair,
)
from repro.core.sem import interpolation_matrix


def _asm(prob):
    return assembly(prob.l2g, prob.n_degree, prob.mesh.shape)


@pytest.fixture(scope="module")
def prob64():
    jax.config.update("jax_enable_x64", True)
    return build_problem(4, (3, 2, 2), lam=0.7, deform=0.2, dtype=jnp.float64)


def _dense(f, n):
    return np.array(jax.vmap(f, in_axes=1, out_axes=1)(jnp.eye(n)))


def test_local_operator_columns_matches_per_column(prob64):
    """The probing helper equals column-by-column local_poisson applies."""
    from repro.core.operator import local_poisson

    rng = np.random.default_rng(0)
    cols = jnp.asarray(rng.standard_normal((prob64.g.shape[-1], 3)))
    got = local_operator_columns(
        prob64.g, prob64.d, prob64.lam, prob64.w_local, cols
    )
    e = prob64.g.shape[0]
    for k in range(cols.shape[1]):
        want = local_poisson(
            jnp.broadcast_to(cols[:, k], (e, cols.shape[0])),
            prob64.g, prob64.d, prob64.lam, prob64.w_local,
        )
        np.testing.assert_allclose(np.array(got[:, :, k]), np.array(want))


def test_materialized_equals_chained_triple_product(prob64):
    """Z_cᵀ[Ĵᵀ(S_L+λW)Ĵ]Z_c == R A P exactly (to roundoff), levels 1 and 2,
    on a deformed mesh — the embedding identity the materialization rests on."""
    a = poisson_assembled(prob64)
    pc1 = coarsen_problem(prob64, 2)
    prolong, restrict = make_transfer_pair(prob64, pc1)
    want1 = _dense(lambda v: restrict(a(prolong(v))), pc1.n_global)
    blocks1 = galerkin_element_blocks(
        prob64.g, prob64.d, prob64.lam, prob64.w_local, 2
    )
    got1 = _dense(
        galerkin_block_apply(blocks1, _asm(pc1)), pc1.n_global
    )
    np.testing.assert_allclose(got1, want1, atol=1e-12)

    # level 2: coarsen the *blocks*; chain the transfers for the reference
    pc2 = coarsen_problem(pc1, 1)
    p2, r2 = make_transfer_pair(pc1, pc2)
    want2 = _dense(lambda v: r2(restrict(a(prolong(p2(v))))), pc2.n_global)
    blocks2 = coarsen_element_blocks(blocks1, interpolation_matrix(1, 2))
    got2 = _dense(
        galerkin_block_apply(blocks2, _asm(pc2)), pc2.n_global
    )
    np.testing.assert_allclose(got2, want2, atol=1e-12)

    # blocks are exactly symmetric; exact diagonal cross-checks the assembly
    np.testing.assert_array_equal(
        np.array(blocks1), np.array(blocks1.transpose(0, 2, 1))
    )
    np.testing.assert_allclose(
        np.array(galerkin_assembled_diagonal(blocks1, _asm(pc1))),
        np.diag(want1),
        atol=1e-12,
    )


@pytest.mark.parametrize("coefficient", ["smooth", "checker"])
def test_galerkin_probing_is_coefficient_agnostic(coefficient):
    """ISSUE tentpole: the block probe consumes exactly the streams the
    fine operator does — variable k rides the folded g and λ(x) rides the
    screen stream — so Z_cᵀ[Ĵᵀ(S_k+JWλ)Ĵ]Z_c == R A P with no
    coefficient-aware code anywhere in the probing path."""
    from repro.core.operator import screen_stream

    jax.config.update("jax_enable_x64", True)
    prob = build_problem(
        4, (2, 2, 2), lam=0.7, deform=0.2, dtype=jnp.float64,
        coefficient=coefficient,
    )
    a = poisson_assembled(prob)
    pc1 = coarsen_problem(prob, 2)
    prolong, restrict = make_transfer_pair(prob, pc1)
    want = _dense(lambda v: restrict(a(prolong(v))), pc1.n_global)
    w_eff, lam_eff = screen_stream(prob)
    blocks = galerkin_element_blocks(prob.g, prob.d, lam_eff, w_eff, 2)
    got = _dense(
        galerkin_block_apply(blocks, _asm(pc1)), pc1.n_global
    )
    np.testing.assert_allclose(got, want, atol=1e-12)


def test_ladder_blocks_match_per_level_probing(prob64):
    """galerkin_ladder_blocks (probe once, contract deeper) equals probing
    the fine operator independently at every coarse degree."""
    ladder = galerkin_ladder_blocks(
        prob64.g, prob64.d, prob64.lam, prob64.w_local, (4, 2, 1)
    )
    for nc, blocks in zip((2, 1), ladder):
        direct = galerkin_element_blocks(
            prob64.g, prob64.d, prob64.lam, prob64.w_local, nc
        )
        np.testing.assert_allclose(
            np.array(blocks), np.array(direct), atol=1e-12
        )


def test_galerkin_mat_vcycle_matches_chained_and_is_spd(prob64):
    """The galerkin_mat V-cycle is the chained-galerkin V-cycle to roundoff
    (same matrix, materialized) and stays a symmetric positive-definite map."""
    a = poisson_assembled(prob64)
    pc_chained, _ = make_pmg_preconditioner(prob64, a, coarse_op="galerkin")
    pc_mat, info = make_pmg_preconditioner(prob64, a, coarse_op="galerkin_mat")
    assert info.coarse_op == "galerkin_mat"
    m_chained = _dense(pc_chained, prob64.n_global)
    m_mat = _dense(pc_mat, prob64.n_global)
    scale = np.abs(m_chained).max()
    assert np.abs(m_mat - m_chained).max() < 1e-12 * scale
    np.testing.assert_allclose(m_mat, m_mat.T, atol=1e-12)
    assert np.linalg.eigvalsh(0.5 * (m_mat + m_mat.T)).min() > 0


def test_galerkin_mat_zero_fine_applies_per_coarse_apply(prob64):
    """ISSUE acceptance: materialized coarse applies never invoke the fine
    operator — per V-cycle the galerkin_mat fine-apply count equals the
    rediscretized count (fine-level smoothing + residual only), while the
    chained form pays extra fine applies for every coarse-level visit."""
    a = poisson_assembled(prob64)
    counts = {}
    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.standard_normal(prob64.n_global))
    for coarse_op in ("redisc", "galerkin", "galerkin_mat"):
        calls = {"n": 0}

        def counting_a(v, _calls=calls):
            _calls["n"] += 1
            return a(v)

        pc, _ = make_pmg_preconditioner(prob64, counting_a, coarse_op=coarse_op)
        calls["n"] = 0          # discard setup-time (spectrum) applies
        jax.block_until_ready(pc(r))
        counts[coarse_op] = calls["n"]
    assert counts["galerkin_mat"] == counts["redisc"], counts
    assert counts["galerkin"] > counts["galerkin_mat"], counts

    # and the materialized coarse operator itself makes zero fine applies
    calls = {"n": 0}

    def counting_a2(v):
        calls["n"] += 1
        return a(v)

    blocks = galerkin_element_blocks(
        prob64.g, prob64.d, prob64.lam, prob64.w_local, 2
    )
    pc1 = coarsen_problem(prob64, 2)
    coarse = galerkin_block_apply(blocks, _asm(pc1))
    jax.block_until_ready(coarse(jnp.ones(pc1.n_global)))
    assert calls["n"] == 0


def test_galerkin_mat_iteration_parity_small(prob64):
    """galerkin_mat walks the same PCG iterates as chained galerkin."""
    a = poisson_assembled(prob64)
    rng = np.random.default_rng(1)
    b = jnp.asarray(rng.standard_normal(prob64.n_global))
    iters = {}
    for coarse_op in ("galerkin", "galerkin_mat"):
        pc, _ = make_preconditioner(
            "pmg", prob64, a, pmg_coarse_op=coarse_op
        )
        res = cg_assembled(a, b, n_iter=300, tol=1e-10, precond=pc)
        assert int(res.iterations) < 300
        iters[coarse_op] = int(res.iterations)
        rel = np.linalg.norm(np.array(a(res.x) - b)) / np.linalg.norm(
            np.array(b)
        )
        assert rel < 1e-8
    assert iters["galerkin_mat"] == iters["galerkin"], iters


def test_galerkin_mat_mixed_within_one_iteration():
    """fp32-assembled blocks behind the cast boundary: within +1 iteration
    of the fp64 galerkin_mat solve (flexible β)."""
    jax.config.update("jax_enable_x64", True)
    prob = build_problem(4, (3, 2, 2), lam=0.1, deform=0.2, dtype=jnp.float64)
    a = poisson_assembled(prob)
    rng = np.random.default_rng(2)
    b = jnp.asarray(rng.standard_normal(prob.n_global))
    iters = {}
    for pdt in (None, jnp.float32):
        pc, info = make_preconditioner(
            "pmg", prob, a, pmg_coarse_op="galerkin_mat", precond_dtype=pdt
        )
        if pdt is not None:
            assert info.dtype == "float32"
        res = cg_assembled(
            a, b, n_iter=300, tol=1e-8, precond=pc,
            cg_variant="standard" if pdt is None else "flexible",
        )
        assert int(res.iterations) < 300
        iters[pdt] = int(res.iterations)
    assert iters[jnp.float32] <= iters[None] + 1, iters


def test_acceptance_n7_small_lambda_parity():
    """ISSUE acceptance: on the PR 3 case (N=7, λ=0.1, tol=1e-8) the
    materialized form reproduces chained Galerkin iteration-for-iteration
    and keeps the gap closed vs rediscretized pmg."""
    jax.config.update("jax_enable_x64", True)
    prob = build_problem(7, (4, 4, 4), lam=0.1, deform=0.15, dtype=jnp.float64)
    a = poisson_assembled(prob)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(prob.n_global))
    iters = {}
    for coarse_op in ("redisc", "galerkin", "galerkin_mat"):
        pc, _ = make_preconditioner("pmg", prob, a, pmg_coarse_op=coarse_op)
        res = cg_assembled(a, b, n_iter=500, tol=1e-8, precond=pc)
        assert int(res.iterations) < 500
        iters[coarse_op] = int(res.iterations)
    assert iters["galerkin_mat"] == iters["galerkin"], iters
    assert iters["galerkin_mat"] < iters["redisc"], iters


def test_pallas_block_matvec_matches_ref():
    """kernels.ops.block_matvec (interpret mode) == the einsum reference,
    incl. element counts that don't divide the block size."""
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    for e, p, dt in ((7, 27, jnp.float32), (13, 125, jnp.float64)):
        b = jnp.asarray(rng.standard_normal((e, p, p)), dt)
        u = jnp.asarray(rng.standard_normal((e, p)), dt)
        got = ops.block_matvec(b, u, block_e=4, interpret=True)
        np.testing.assert_allclose(
            np.array(got), np.array(ref.block_matvec_ref(b, u)), rtol=1e-6
        )


def test_galerkin_matvec_injection(prob64):
    """make_pmg_preconditioner(galerkin_matvec=...) routes coarse applies
    through the injected batched matvec (the Pallas wiring hook)."""
    from repro.kernels import ops

    a = poisson_assembled(prob64)
    pc_default, _ = make_pmg_preconditioner(
        prob64, a, coarse_op="galerkin_mat"
    )
    pc_pallas, _ = make_pmg_preconditioner(
        prob64, a, coarse_op="galerkin_mat",
        galerkin_matvec=ops.make_block_matvec(interpret=True),
    )
    rng = np.random.default_rng(3)
    r = jnp.asarray(rng.standard_normal(prob64.n_global))
    np.testing.assert_allclose(
        np.array(pc_pallas(r)), np.array(pc_default(r)), rtol=1e-12
    )


def test_dist_galerkin_chained_raises():
    """The chained form stays single-device: dist_cg must refuse it loudly
    rather than silently rediscretizing."""
    from repro.comms.topology import ProcessGrid
    from repro.core.distributed import build_dist_problem, dist_cg

    grid = ProcessGrid((1, 1, 1))
    prob = build_dist_problem(2, grid, (2, 2, 2), dtype=jnp.float64)
    with pytest.raises(NotImplementedError, match="galerkin_mat"):
        dist_cg(prob, None, None, precond="pmg", pmg_coarse_op="galerkin")


def test_dist_galerkin_mat_matches_single_shard():
    """ISSUE acceptance: sharded pmg_coarse_op="galerkin_mat" matches the
    single-shard solve iteration-for-iteration at fp64 AND under
    precond_dtype=fp32, and beats rediscretized dist pmg at small λ."""
    run_subprocess(
        """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core.distributed import build_dist_problem, dist_cg
from repro.comms.topology import ProcessGrid
from repro.core import build_problem, poisson_assembled, cg_assembled
from repro.core.precond import make_preconditioner

N = 3
grid = ProcessGrid((2, 2, 2)); local = (2, 1, 1)
gshape = (4, 2, 2)
ref = build_problem(N, gshape, lam=0.1, dtype=jnp.float64)
A = poisson_assembled(ref)
mesh = make_mesh((8,), ("ranks",))
prob = build_dist_problem(N, grid, local, lam=0.1, dtype=jnp.float64)
rng = np.random.default_rng(0)
bg = rng.standard_normal(ref.n_global)
GX, GY = gshape[0]*N+1, gshape[1]*N+1
def box_from_global(vec):
    out = np.zeros((grid.size, prob.m3))
    mx, my, mz = prob.box_shape
    for r in range(grid.size):
        ci, cj, ck = grid.coords(r)
        ox, oy, oz = ci*local[0]*N, cj*local[1]*N, ck*local[2]*N
        x, y, z = np.meshgrid(np.arange(mx), np.arange(my), np.arange(mz), indexing="ij")
        gidx = (ox+x) + GX*((oy+y) + GY*(oz+z))
        out[r] = vec[gidx.transpose(2,1,0).reshape(-1)]
    return out
b_boxes = jnp.asarray(box_from_global(bg))
it_mat = {}
for pdtype, variant in ((None, "standard"), (jnp.float32, "flexible")):
    run = jax.jit(dist_cg(prob, mesh, b_boxes, n_iter=200, tol=1e-10,
                          precond="pmg", pmg_coarse_op="galerkin_mat",
                          precond_dtype=pdtype, cg_variant=variant))
    x_boxes, rdotr, iters, status, hist = run()
    assert int(iters) < 200, int(iters)
    pc, info = make_preconditioner("pmg", ref, A,
                                   pmg_coarse_op="galerkin_mat",
                                   precond_dtype=pdtype)
    res = cg_assembled(A, jnp.asarray(bg), n_iter=200, tol=1e-10, precond=pc,
                       cg_variant=variant)
    assert int(iters) == int(res.iterations), (
        pdtype, int(iters), int(res.iterations))
    err = np.abs(np.array(x_boxes) - box_from_global(np.array(res.x))).max()
    assert err < 1e-8, (pdtype, err)
    it_mat[pdtype] = int(iters)
run = jax.jit(dist_cg(prob, mesh, b_boxes, n_iter=200, tol=1e-10,
                      precond="pmg"))
_, _, it_redisc, _, _ = run()
assert it_mat[None] < int(it_redisc), (it_mat, int(it_redisc))
print("OK", it_mat, int(it_redisc))
"""
    )
