"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_problem
from repro.kernels import ops, ref


@pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 9, 15])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_poisson_kernel_matches_oracle(n, dtype, rng):
    shape = (2, 2, 2) if n > 7 else (3, 2, 2)
    prob = build_problem(n, shape, lam=1.3, deform=0.1, dtype=dtype)
    e, p = prob.mesh.n_elements, prob.mesh.points_per_element
    u = jnp.asarray(rng.standard_normal((e, p)), dtype)
    want = ref.poisson_local_ref(u, prob.g, prob.w_local, prob.d, lam=1.3)
    got = ops.poisson_local(
        u, prob.g, prob.w_local, prob.d, lam=1.3, interpret=True
    )
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) / scale < 3e-6


@pytest.mark.parametrize("block_e", [1, 2, 4, 8])
def test_poisson_kernel_block_sweep(block_e, rng):
    prob = build_problem(4, (3, 1, 1), lam=0.5, deform=0.05, dtype=jnp.float32)
    e, p = prob.mesh.n_elements, prob.mesh.points_per_element
    u = jnp.asarray(rng.standard_normal((e, p)), jnp.float32)
    want = ref.poisson_local_ref(u, prob.g, prob.w_local, prob.d, lam=0.5)
    got = ops.poisson_local(
        u, prob.g, prob.w_local, prob.d, lam=0.5, block_e=block_e, interpret=True
    )
    np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("coefficient", ["smooth", "checker"])
@pytest.mark.parametrize("deform", [0.0, 0.15])
def test_poisson_kernel_variable_coefficient_fp64(coefficient, deform, rng):
    """Variable k(x)/λ(x) reach the Pallas kernel only through the folded
    g factors and the mass-weighted w stream (``screen_stream``) — parity
    with the jnp oracle stays at fp64 round-off, deformed coords included."""
    import jax

    from repro.core.operator import screen_stream

    jax.config.update("jax_enable_x64", True)
    prob = build_problem(
        4, (2, 2, 2), lam=0.7, deform=deform, dtype=jnp.float64,
        coefficient=coefficient, bc="mixed",
    )
    w_eff, lam_eff = screen_stream(prob)
    e, p = prob.mesh.n_elements, prob.mesh.points_per_element
    u = jnp.asarray(rng.standard_normal((e, p)), jnp.float64)
    want = ref.poisson_local_ref(u, prob.g, w_eff, prob.d, lam=lam_eff)
    got = ops.poisson_local(
        u, prob.g, w_eff, prob.d, lam=lam_eff, interpret=True
    )
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel <= 1e-12


def test_poisson_kernel_bf16(rng):
    prob = build_problem(3, (2, 2, 2), lam=1.0, dtype=jnp.bfloat16)
    e, p = prob.mesh.n_elements, prob.mesh.points_per_element
    u = jnp.asarray(rng.standard_normal((e, p)), jnp.bfloat16)
    want = ref.poisson_local_ref(u, prob.g, prob.w_local, prob.d, lam=1.0)
    got = ops.poisson_local(u, prob.g, prob.w_local, prob.d, lam=1.0, interpret=True)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    err = float(jnp.max(jnp.abs((got - want).astype(jnp.float32))))
    assert err / scale < 0.05  # bf16 tolerance


def test_vmem_budget_picks_smaller_blocks():
    from repro.kernels.poisson import (
        KERNEL_VMEM_BUDGET,
        KERNEL_VMEM_LIMIT,
        pick_block_e,
        vmem_bytes_per_block,
    )

    assert pick_block_e(15) <= pick_block_e(7) or pick_block_e(7) == 256
    for n in (7, 15):
        eb = pick_block_e(n)
        assert vmem_bytes_per_block(eb, n + 1) <= KERNEL_VMEM_BUDGET
        assert KERNEL_VMEM_BUDGET <= KERNEL_VMEM_LIMIT
        # a tighter budget picks a smaller block
        small = pick_block_e(n, budget_bytes=KERNEL_VMEM_BUDGET // 4)
        assert small < eb


@pytest.mark.parametrize("n", [64, 128, 1000, 128 * 9, 40000])
def test_stream_kernels_match_oracle(n, rng):
    r = jnp.asarray(rng.standard_normal(n), jnp.float32)
    ap = jnp.asarray(rng.standard_normal(n), jnp.float32)
    alpha = jnp.float32(0.37)
    rn, rr = ops.fused_axpy_dot(r, ap, alpha, interpret=True)
    rn2, rr2 = ref.fused_axpy_dot_ref(r, ap, alpha)
    np.testing.assert_allclose(np.array(rn), np.array(rn2), atol=1e-6)
    assert abs(float(rr - rr2)) / float(rr2) < 1e-5

    out = ops.fused_xpay(r, ap, alpha, interpret=True)
    np.testing.assert_allclose(
        np.array(out), np.array(ref.fused_xpay_ref(r, ap, alpha)), atol=1e-6
    )

    w = jnp.abs(jnp.asarray(rng.standard_normal(n), jnp.float32))
    wd = ops.weighted_dot(w, r, ap, interpret=True)
    wd2 = ref.weighted_dot_ref(w, r, ap)
    assert abs(float(wd - wd2)) <= 1e-4 * abs(float(wd2)) + 1e-4


@pytest.mark.parametrize("b,n", [(1, 256), (3, 1000), (16, 128 * 9)])
def test_batched_stream_kernels_match_oracle(b, n, rng):
    r = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    ap = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    alpha = jnp.asarray(rng.standard_normal(b), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(b), jnp.float32)
    dinv = jnp.asarray(rng.standard_normal(n) ** 2 + 0.1, jnp.float32)

    rn, rr = ops.fused_axpy_dot_batched(r, ap, alpha, interpret=True)
    rn2, rr2 = ref.fused_axpy_dot_batched_ref(r, ap, alpha)
    assert rn.shape == (b, n) and rr.shape == (b,)
    np.testing.assert_allclose(np.array(rn), np.array(rn2), atol=1e-6)
    np.testing.assert_allclose(np.array(rr), np.array(rr2), rtol=1e-5)

    out = ops.fused_xpay_batched(r, ap, beta, interpret=True)
    np.testing.assert_allclose(
        np.array(out), np.array(ref.fused_xpay_batched_ref(r, ap, beta)), atol=1e-6
    )

    z, rz = ops.fused_jacobi_dot_batched(dinv, r, interpret=True)
    z2, rz2 = ref.fused_jacobi_dot_batched_ref(dinv, r)
    np.testing.assert_allclose(np.array(z), np.array(z2), atol=1e-6)
    np.testing.assert_allclose(np.array(rz), np.array(rz2), rtol=1e-5)


def test_batched_stream_kernels_row_equals_unbatched(rng):
    """Each column of the 2-D layout does the unbatched kernel's arithmetic
    bit-for-bit — the property the batched solver's per-column parity
    guarantee rests on."""
    b, n = 4, 1024
    r = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    ap = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    alpha = jnp.asarray(rng.standard_normal(b), jnp.float32)
    dinv = jnp.asarray(rng.standard_normal(n) ** 2 + 0.1, jnp.float32)
    rn, rr = ops.fused_axpy_dot_batched(r, ap, alpha, interpret=True)
    z, rz = ops.fused_jacobi_dot_batched(dinv, r, interpret=True)
    out = ops.fused_xpay_batched(r, ap, alpha, interpret=True)
    for i in range(b):
        rn1, rr1 = ops.fused_axpy_dot(r[i], ap[i], alpha[i], interpret=True)
        assert np.array_equal(np.array(rn[i]), np.array(rn1))
        assert float(rr[i]) == float(rr1)
        z1, rz1 = ops.fused_jacobi_dot(dinv, r[i], interpret=True)
        assert np.array_equal(np.array(z[i]), np.array(z1))
        assert float(rz[i]) == float(rz1)
        out1 = ops.fused_xpay(r[i], ap[i], alpha[i], interpret=True)
        assert np.array_equal(np.array(out[i]), np.array(out1))


def test_batched_stream_kernels_pin_vmap_semantics(rng):
    """vmap of the unbatched stages (what batched_cg_assembled lowers the
    per-column fused closures through) computes exactly the explicit 2-D
    batched kernels."""
    import jax

    b, n = 3, 640
    r = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    ap = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    alpha = jnp.asarray(rng.standard_normal(b), jnp.float32)
    rn_v, rr_v = jax.vmap(
        lambda r_i, ap_i, a_i: ops.fused_axpy_dot(r_i, ap_i, a_i, interpret=True)
    )(r, ap, alpha)
    rn_b, rr_b = ops.fused_axpy_dot_batched(r, ap, alpha, interpret=True)
    assert np.array_equal(np.array(rn_v), np.array(rn_b))
    assert np.array_equal(np.array(rr_v), np.array(rr_b))


def test_batched_jacobi_adapter_mixed_precision(rng):
    b, n = 2, 384
    dinv = jnp.asarray(rng.standard_normal(n) ** 2 + 0.1, jnp.float32)
    r = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    f = ops.make_fused_jacobi_dot_batched(dinv, interpret=True)
    z, rz = f(r)
    z2, rz2 = ref.fused_jacobi_dot_batched_ref(dinv, r)
    np.testing.assert_allclose(np.array(z), np.array(z2), atol=1e-6)
    np.testing.assert_allclose(np.array(rz), np.array(rz2), rtol=1e-5)


def test_assembled_operator_with_pallas_kernel(rng):
    from repro.core import poisson_assembled

    prob = build_problem(5, (2, 2, 2), lam=0.9, deform=0.12, dtype=jnp.float32)
    a_ref = poisson_assembled(prob)
    a_pl = poisson_assembled(prob, local_op=ops.make_local_op(interpret=True))
    x = jnp.asarray(rng.standard_normal(prob.n_global), jnp.float32)
    want = a_ref(x)
    got = a_pl(x)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) / scale < 3e-6
