"""Lattice Z / Z^T: the box mesh's gather and scatter as slices, pads and adds.

The lattice pair must be the indexed pair (``take`` / ``segment_sum``) on
the box lattice numbering: Z exactly, Z^T up to the order of each shared
point's sum. ``assembly`` chooses it only where the map is that lattice, of
a whole box or of element blocks of it; ``poisson_assembled`` takes its Z
and Z^T from there.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import build_box_mesh, build_problem, poisson_assembled
from repro.core.distributed import _element_blocks, _local_l2g
from repro.core.gather_scatter import (
    assembly,
    gather,
    indexed_assembly,
    lattice_gather,
    lattice_scatter,
    scatter,
)
from repro.core.mesh import lattice_l2g

jax.config.update("jax_enable_x64", True)

CASES = [(n, shape) for n in (1, 2, 3, 7) for shape in ((4, 3, 2), (1, 1, 1), (3, 1, 2))]
z_idx = jax.jit(scatter)
zt_idx = jax.jit(gather, static_argnums=2)
DTYPES = [(jnp.float64, 1e-12), (jnp.float32, 4 * np.finfo(np.float32).eps)]


def _data(n, shape, dtype, seed=0):
    m = build_box_mesh(n, shape)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(m.n_global), dtype)
    y = jnp.asarray(rng.standard_normal(m.l2g.shape), dtype)
    return m, x, y


@pytest.mark.parametrize("dtype,tol", DTYPES, ids=["f64", "f32"])
@pytest.mark.parametrize("n,shape", CASES)
def test_lattice_scatter_equals_take(n, shape, dtype, tol):
    m, x, _ = _data(n, shape, dtype)
    got = lattice_scatter(x, shape, n)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(got, z_idx(x, jnp.asarray(m.l2g)))


@pytest.mark.parametrize("dtype,tol", DTYPES, ids=["f64", "f32"])
@pytest.mark.parametrize("n,shape", CASES)
def test_lattice_gather_equals_segment_sum(n, shape, dtype, tol):
    m, _, y = _data(n, shape, dtype)
    got = lattice_gather(y, shape, n)
    want = zt_idx(y, jnp.asarray(m.l2g), m.n_global)
    assert got.shape == (m.n_global,) and got.dtype == y.dtype
    # each shared point sums at most 8 terms, in another order
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("n,shape", [(1, (4, 3, 2)), (3, (2, 1, 3)), (7, (2, 2, 2))])
def test_lattice_pair_is_adjoint(n, shape):
    _, x, y = _data(n, shape, jnp.float64, seed=3)
    lhs = jnp.vdot(lattice_scatter(x, shape, n), y)
    rhs = jnp.vdot(x, lattice_gather(y, shape, n))
    assert abs(float(lhs - rhs)) <= 1e-12 * abs(float(lhs))


def _kind(l2g, shape, n, blocks=None):
    return assembly(l2g, n, shape, blocks).kind


def test_lattice_numbering_is_the_box_mesh_map():
    for n, shape in CASES:
        l2g = build_box_mesh(n, shape).l2g
        assert _kind(l2g, shape, n) == "lattice"
        np.testing.assert_array_equal(lattice_l2g(shape, n), l2g)


def test_is_lattice_rejects_other_maps():
    n, shape = 3, (4, 3, 2)
    l2g = build_box_mesh(n, shape).l2g
    rng = np.random.default_rng(0)
    perm = rng.permutation(l2g.max() + 1)
    indexed = lambda m, s=shape, k=n: _kind(m, s, k) == "indexed"
    assert indexed(perm[l2g])                            # renumbered points
    assert indexed(l2g[rng.permutation(len(l2g))])       # elements
    assert indexed(l2g[:, ::-1])                         # local node order
    swapped = l2g.copy()
    swapped[0, [0, 1]] = swapped[0, [1, 0]]
    assert indexed(swapped)                              # one swap
    assert indexed(l2g, (2, 3, 4))                       # another grid
    assert indexed(l2g, shape, 2)                        # another degree
    assert indexed(l2g[:-1])                             # another shape


def test_is_lattice_on_halo_first_maps():
    # a box with interior elements numbers its halo elements first: not the
    # lattice of the whole box but that of its element blocks; in a box that
    # is all halo the two orders coincide
    l2g, n_halo = _local_l2g(3, (4, 4, 4))
    blocks, _ = _element_blocks((4, 4, 4))
    assert n_halo < 64 and _kind(l2g, (4, 4, 4), 3) == "indexed"
    assert _kind(l2g, (4, 4, 4), 3, blocks) == "lattice"
    assert _kind(l2g, (4, 4, 4), 3, blocks[::-1]) == "indexed"
    l2g, n_halo = _local_l2g(3, (2, 2, 2))
    assert n_halo == 8 and _kind(l2g, (2, 2, 2), 3) == "lattice"


def _part(n, shape, part):
    """(l2g, blocks, kind expected) of one element set of the box ``shape``."""
    if part == "box":
        return lattice_l2g(shape, n), None, "lattice"
    l2g, eh = _local_l2g(n, shape)
    blocks, nh = _element_blocks(shape)
    if part == "halo":
        return l2g[:eh], blocks[:nh], "lattice"
    if part == "interior":
        return l2g[eh:], blocks[nh:], "lattice"
    # the interior with its elements in another order
    perm = np.random.default_rng(0).permutation(len(l2g) - eh)
    return l2g[eh:][perm], blocks[nh:], "indexed"


SEAM = [
    (n, shape, part)
    for n in (1, 2, 3, 7)
    for shape in ((1, 1, 1), (2, 3, 1), (4, 4, 4))
    for part in ("box", "halo", "interior")
    if part != "interior" or min(shape) > 2
] + [(3, (4, 4, 4), "permuted")]


@pytest.mark.parametrize("n,shape,part", SEAM)
def test_assembly_lattice_equals_indexed(n, shape, part):
    """One map's ``assembly`` (lattice where it may be) and
    ``indexed_assembly``: Z bitwise, Z^T to the order of each point's sum."""
    l2g, blocks, kind = _part(n, shape, part)
    n_points = int(np.prod([e * n + 1 for e in shape]))
    got, ref = assembly(l2g, n, shape, blocks), indexed_assembly(l2g, n_points)
    assert (got.kind, ref.kind) == (kind, "indexed")
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal(n_points))
    y = jnp.asarray(rng.standard_normal(l2g.shape))
    np.testing.assert_array_equal(jax.jit(got.z)(x), jax.jit(ref.z)(x))
    want = np.asarray(jax.jit(ref.zt)(y))
    np.testing.assert_allclose(
        jax.jit(got.zt)(y), want, rtol=0, atol=1e-13 * np.abs(want).max()
    )


def _renumbered(prob, seed=0):
    """The same problem with its global points renumbered (not the lattice)."""
    perm = np.random.default_rng(seed).permutation(prob.n_global)
    l2g = perm[np.asarray(prob.l2g)]
    mask = None
    if prob.mask is not None:
        mask = jnp.zeros_like(prob.mask).at[perm].set(prob.mask)
    return dataclasses.replace(prob, l2g=jnp.asarray(l2g), mask=mask), perm


@pytest.mark.parametrize(
    "coefficient,bc", [(None, None), ("smooth", None), ("smooth", "mixed")]
)
def test_lattice_operator_matches_indexed(coefficient, bc):
    prob = build_problem(
        3, (3, 2, 2), deform=0.2, coefficient=coefficient, bc=bc, dtype=jnp.float64
    )
    other, perm = _renumbered(prob)
    a_lat, a_idx = jax.jit(poisson_assembled(prob)), jax.jit(poisson_assembled(other))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(prob.n_global))
    want = np.empty(prob.n_global)
    want[:] = np.asarray(a_idx(jnp.zeros_like(x).at[perm].set(x)))[perm]
    got = np.asarray(a_lat(x))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12 * np.abs(want).max())
    if bc is not None:
        assert np.any(np.asarray(prob.mask) == 0)
        np.testing.assert_array_equal(got[np.asarray(prob.mask) == 0], 0.0)


def test_assembly_attribute_and_tally():
    prob = build_problem(2, (2, 2, 1), dtype=jnp.float64)
    before = obs.tallies()
    a = poisson_assembled(prob)
    b = poisson_assembled(_renumbered(prob)[0])
    c = poisson_assembled(prob, fused=True)  # the in-kernel gather and scatter-add
    assert (a.assembly, b.assembly, c.assembly) == ("lattice", "indexed", "indexed")
    after = obs.tallies()
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in obs.TALLIES}
    assert grew == {**dict.fromkeys(obs.TALLIES, 0),
                    "op.assembly.lattice": 1, "op.assembly.indexed": 2}
    with pytest.raises(ValueError):
        obs.tally("op.assembly.other")


def test_lattice_apply_has_no_gather_or_scatter():
    prob = build_problem(3, (3, 2, 2), deform=0.1, bc="dirichlet", dtype=jnp.float32)
    x = jnp.ones((prob.n_global,), jnp.float32)

    def ops(apply):
        lowered = jax.jit(apply).lower(x)
        return lowered.as_text(), lowered.compile().as_text()

    stable, hlo = ops(poisson_assembled(prob))
    assert "stablehlo.gather" not in stable and "stablehlo.scatter" not in stable
    assert " gather(" not in hlo and " scatter(" not in hlo
    stable, _ = ops(poisson_assembled(_renumbered(prob)[0]))
    assert "stablehlo.gather" in stable and "stablehlo.scatter" in stable
