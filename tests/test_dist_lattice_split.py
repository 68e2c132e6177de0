"""The lattice Z and Zᵀ of the sharded halo/interior split.

Where a rank's map is the lattice of its ``_element_blocks``,
``gather_scatter.assembly`` gives ``_split_apply`` Z and Zᵀ as lattice
slices on the six face slabs and the core of each rank's padded box
(``Assembly.kind == "lattice"``).  The reference is the same problem with
its elements reordered (halo and interior each reversed) in ``l2g``, ``g``,
``w_local`` and ``screen``, which sends it down the indexed take and
segment sum; both run on 4 fake CPU devices in a subprocess.
"""
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core.distributed import _element_blocks, _ordered_elements

SHAPES = [(1, 2, 2), (2, 2, 2), (2, 2, 3), (3, 3, 2), (3, 3, 3), (4, 3, 5)]

SPLIT = """
import dataclasses
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh, shard_map
from repro.comms.topology import ProcessGrid
from repro.core.distributed import (
    _aux_split, _on_rank, _rank_operator, _split_assemblies, _split_kind,
    box_global_indices, build_dist_problem,
)
from repro.core.operator import local_poisson

n, local = {n}, {local}
grid = ProcessGrid((2, 2, 1))
mesh = make_mesh((4,), ("ranks",))
spec = P("ranks")


def reordered(prob):
    # the same problem, halo and interior elements each in reverse order
    eh, e = prob.halo_elems, prob.e_local
    perm = np.r_[np.arange(eh)[::-1], np.arange(eh, e)[::-1]]
    return dataclasses.replace(
        prob, l2g=prob.l2g[perm], g=prob.g[:, perm], w_local=prob.w_local[:, perm],
        screen=None if prob.screen is None else prob.screen[:, perm])


def face_noise(prob, rng):
    # nonzero only on the six face planes of each rank's padded box
    mx, my, mz = prob.box_shape
    face = np.zeros((mz, my, mx), bool)
    face[[0, -1]] = face[:, [0, -1]] = face[:, :, [0, -1]] = True
    return rng.standard_normal((grid.size, prob.m3)) * face.reshape(-1)


def applies(prob, x, x_raw):
    # plain, two-phase, deferred raw twin, and the screened Dirichlet operator
    aux = tuple(a for a in (prob.screen, prob.bc_mask) if a is not None)

    @partial(shard_map, mesh=mesh, in_specs=(spec,) * 4 + (tuple(spec for _ in aux),),
             out_specs=(spec,) * 4)
    def run(x_s, raw_s, g_s, w_s, aux_s):
        x1 = x_s[0]
        rank = _on_rank(prob, g_s[0], w_s[0], None, *_aux_split(prob, aux_s))
        plain = _on_rank(prob, g_s[0], w_s[0], None, rank.screen)  # no bc mask
        apply = partial(_rank_operator, local_op=local_poisson)
        outs = (apply(plain, two_phase=False)(x1), apply(plain, two_phase=True)(x1),
                apply(plain, two_phase=False)(x1, raw_s[0]),
                apply(rank, two_phase=False)(x1))
        return tuple(o[None] for o in outs)

    return [np.asarray(o) for o in jax.jit(run)(x, x_raw, prob.g, prob.w_local, aux)]


rng = np.random.default_rng(7)
for kw in ({{}}, {{"coefficient": "smooth", "bc": "mixed"}}):
    lat = build_dist_problem(n, grid, local, lam=0.7, dtype=jnp.float64, **kw)
    idx = reordered(lat)
    assert _split_kind(lat) == "lattice" and _split_kind(idx) == "indexed"
    assert [a.kind for a in _split_assemblies(lat)] == ["lattice"] * 2
    assert _split_assemblies(idx)[0].kind == "indexed"
    assert (lat.screen is None) == (not kw)
    xg = rng.standard_normal(lat.n_global)
    x = jnp.asarray(xg[box_global_indices(lat)])
    x_raw = x + face_noise(lat, rng)
    got, want = applies(lat, x, x_raw), applies(idx, x, x_raw)
    for name, a, b in zip(("plain", "two_phase", "x_raw", "screen_bc"), got, want):
        err = np.abs(a - b).max() / np.abs(b).max()
        print(kw, name, err)
        assert err < 1e-13, (kw, name, err)
    # the interior never reads a face plane: the raw twin changes nothing
    err = np.abs(got[2] - got[0]).max() / np.abs(got[0]).max()
    assert err < 1e-13, err
print("OK")
"""


@pytest.mark.parametrize("local", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("n", [3, 7])
def test_lattice_split_equals_indexed_split(n, local):
    """Plain, two-phase, raw-twin and screened Dirichlet applies of the
    lattice split equal the indexed split's to 1e-13 relative (float64)."""
    run_subprocess(SPLIT.format(n=n, local=local), devices=4)


@pytest.mark.parametrize(
    "local", SHAPES + [(1, 1, 1), (3, 4, 5), (5, 5, 5), (16, 16, 16)],
    ids=lambda s: "x".join(map(str, s)),
)
def test_ordered_elements_halo_first(local):
    """Every element once; the first ``halo_elems`` touch a face of the
    rank box, the rest none; each block in its own lattice order."""
    ordered, eh = _ordered_elements(local)
    b = np.asarray(local)
    assert sorted(map(tuple, ordered)) == sorted(np.ndindex(*local))
    on_face = ((ordered == 0) | (ordered == b - 1)).any(axis=1)
    assert on_face[:eh].all() and not on_face[eh:].any()
    assert eh == np.prod(b) - np.prod(np.maximum(b - 2, 0))
    blocks, n_halo_blocks = _element_blocks(local)
    assert sum(np.prod(s) for _, s in blocks[:n_halo_blocks]) == eh
    start = 0
    for origin, shape in blocks:
        e = int(np.prod(shape))
        rel = ordered[start:start + e] - np.asarray(origin)
        i, j, k = rel.T
        assert np.array_equal(i + shape[0] * (j + shape[1] * k), np.arange(e))
        start += e
    assert start == len(ordered)
