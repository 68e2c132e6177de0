"""The sharded smoother diagonal and pMG transfers are the single-device ones.

On a rank, ``precond.assembled_diagonal`` and ``precond.make_transfer_pair``
run on the rank's view of its level and its padded box's level assembly,
completed by the level's sum-exchange.  On the same global problem they
must equal the single-device forms (float64), and each transfer's raw box
must already hold the final values on every slot off the rank box's faces.
"""
import pytest

from conftest import run_subprocess

LEVELS = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh, shard_map
from repro.comms.topology import ProcessGrid
from repro.core import build_problem
from repro.core.distributed import (
    _XCH, _aux_operands, _aux_split, _completion, _level_assembly, _on_rank,
    _rank_dinv, box_global_indices, build_dist_problem, build_pmg_levels,
)
from repro.core.operator import coarsen_problem
from repro.core.precond import assembled_diagonal, make_transfer_pair, masked_dinv

grid, local, n, nc = ProcessGrid({grid}), (3, 2, 2), 3, 2
kw = {kw}
mesh = make_mesh((grid.size,), ("ranks",))
spec = P("ranks")
one = build_problem(n, tuple(g * b for g, b in zip(grid.shape, local)), lam=0.7,
                    dtype=jnp.float64, **kw)
one_c = coarsen_problem(one, nc)
dist = build_dist_problem(n, grid, local, lam=0.7, dtype=jnp.float64, **kw)
levels, _ = build_pmg_levels(dist, (n, nc))
fine, coarse = (box_global_indices(lvl) for lvl in levels)
rng = np.random.default_rng(3)
xc, rf = rng.standard_normal(one_c.n_global), rng.standard_normal(one.n_global)
aux = tuple(_aux_operands(lvl) for lvl in levels)


@partial(shard_map, mesh=mesh, in_specs=(spec,) * 8 + (jax.tree.map(lambda _: spec, aux),),
         out_specs=(spec,) * 5)
def run(xc_s, rf_s, gf, wf, mf, gc, wc, mc, aux_s):
    rf_, rc_ = (_on_rank(lvl, g[0], w[0], m[0], *_aux_split(lvl, a))
                for lvl, g, w, m, a in zip(levels, (gf, gc), (wf, wc), (mf, mc), aux_s))
    prolong, restrict = make_transfer_pair(
        rf_, rc_, _level_assembly(rf_), _level_assembly(rc_),
        _completion(rf_, _XCH, "prolong"), _completion(rc_, _XCH, "restrict"))
    outs = (_rank_dinv(rf_),) + prolong(xc_s[0]) + restrict(rf_s[0])
    return tuple(o[None] for o in outs)


dinv, p_raw, p_con, r_raw, r_con = (np.asarray(o) for o in jax.jit(run)(
    jnp.asarray(xc[coarse]), jnp.asarray(rf[fine]),
    *(a for lvl in levels for a in (lvl.g, lvl.w_local, lvl.mask)), aux))
prolong1, restrict1 = make_transfer_pair(one, one_c)
want = {{
    "dinv": (dinv, np.asarray(masked_dinv(one, assembled_diagonal(one)))[fine]),
    "prolong": (p_con, np.asarray(prolong1(jnp.asarray(xc)))[fine]),
    "restrict": (r_con, np.asarray(restrict1(jnp.asarray(rf)))[coarse]),
}}
for name, (got, ref) in want.items():
    err = np.abs(got - ref).max() / np.abs(ref).max()
    print(name, err)
    assert err < 1e-13, (name, err)


def off_faces(lvl):
    mx, my, mz = lvl.box_shape
    inner = np.zeros((mz, my, mx), bool)
    inner[1:-1, 1:-1, 1:-1] = True
    return inner.reshape(-1)


for lvl, raw, con in ((levels[0], p_raw, p_con), (levels[1], r_raw, r_con)):
    inner = off_faces(lvl)
    assert inner.any()
    assert np.array_equal(raw[:, inner], con[:, inner])
print("OK")
"""


@pytest.mark.parametrize(
    "kw", [{}, {"coefficient": "smooth", "bc": "mixed"}], ids=["const", "smooth_mixed"]
)
@pytest.mark.parametrize("grid", [(1, 1, 1), (2, 1, 1)], ids=["1x1x1", "2x1x1"])
def test_sharded_diagonal_and_transfers_equal_single_device(grid, kw):
    """The rank's diagonal and (prolong, restrict) against the single-device
    ones to 1e-13 relative; raw off-face slots bitwise equal consistent ones."""
    run_subprocess(
        LEVELS.format(grid=grid, kw=kw), devices=grid[0] * grid[1] * grid[2]
    )
