"""The sharded operator and solver entry points of ``core/distributed.py``.

``dist_solver`` (one compiled solve for many right-hand sides) and its
``solve.operator`` (the outer A-apply the solve iterates with, on its
own), ``box_global_indices``, the upload laid out over the mesh, and the
set-up spans and tallies, on 4 fake CPU devices in a subprocess, as the
other sharded tests run.
"""
import pytest

from conftest import run_subprocess

PRELUDE = """
import dataclasses
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import obs
from repro.compat import make_mesh
from repro.comms.topology import ProcessGrid
from repro.core import build_problem, poisson_assembled
from repro.core import distributed as dist
from repro.core.distributed import (
    box_global_indices, build_dist_problem, dist_cg, dist_solver,
)

grid = ProcessGrid((2, 2, 1))
mesh = make_mesh((4,), ("ranks",))
boxes = NamedSharding(mesh, P("ranks"))


def setup(n, local):
    gshape = tuple(g * e for g, e in zip(grid.shape, local))
    prob = build_dist_problem(n, grid, local, lam=0.7, dtype=jnp.float64)
    ref = build_problem(n, gshape, lam=0.7, dtype=jnp.float64)
    idx = box_global_indices(prob)
    return prob, ref, idx


def to_boxes(vec, idx):
    return jax.device_put(jnp.asarray(np.asarray(vec)[idx]), boxes)


def operator_of(solve):
    # the solve's A-apply, jitted, on boxes
    op = jax.jit(solve.operator)
    return lambda x: op(x, *solve.operator_operands)
"""


@pytest.mark.parametrize("n,local", [(3, (3, 3, 2)), (7, (2, 2, 2))])
def test_dist_operator_equals_the_single_device_operator(n, local):
    """A on consistent boxes equals poisson_assembled on the global vector,
    at every replica, to float64 rounding."""
    run_subprocess(
        PRELUDE
        + f"""
prob, ref, idx = setup({n}, {local})
xg = np.random.default_rng(3).standard_normal(ref.n_global)
yg = np.asarray(poisson_assembled(ref)(jnp.asarray(xg)))
solve = dist_solver(prob, mesh, n_iter=1)
y = np.asarray(operator_of(solve)(to_boxes(xg, idx)))
err = np.abs(y - yg[idx]).max() / np.abs(yg).max()
print("operator rel err", err)
assert err < 1e-13, err
# its operands are the solve's own sharded arrays, not copies
ops = solve.operator_operands
assert ops[0] is solve.operands[0] and ops[1] is solve.operands[1]
assert ops[2] is solve.operands[4]
print("OK")
""",
        devices=4,
    )


def test_dist_solver_compiles_once_and_equals_dist_cg():
    """Two right-hand sides through one dist_solver compile one program, and
    each returns what dist_cg returns for that b, bit for bit."""
    run_subprocess(
        PRELUDE
        + """
prob, ref, idx = setup(3, (2, 2, 2))
rng = np.random.default_rng(0)
bs = [to_boxes(rng.standard_normal(ref.n_global), idx) for _ in range(2)]
solve = dist_solver(prob, mesh, n_iter=25, record_history=True)
obs.reset()
outs = [solve(b) for b in bs]
jax.block_until_ready(outs)
compiles = sum(c["compiles"] for c in obs.counters().values())
print("compiles", compiles)
assert compiles == 1, compiles
assert not np.array_equal(np.asarray(outs[0][0]), np.asarray(outs[1][0]))
for b, got in zip(bs, outs):
    run = dist_cg(prob, mesh, b, n_iter=25, record_history=True)
    for want in (jax.jit(run)(), jax.jit(run.func)(*run.args)):
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
    assert int(got[2]) == 25
print("OK")
""",
        devices=4,
    )


def test_dist_cg_applies_the_dist_operator_bit_for_bit():
    """The A-apply inside the solve's loop and ``solve.operator`` give the
    same boxes, bit for bit, for the same input boxes, under the solve's
    options: a spy on the one per-rank construction records what the
    solve's operator returned."""
    run_subprocess(
        PRELUDE
        + """
prob, ref, idx = setup(3, (3, 3, 2))
b = to_boxes(np.random.default_rng(1).standard_normal(ref.n_global), idx)
real = dist._rank_operator


def spy(prob_, *args, **kw):
    calls.append({k: v for k, v in kw.items() if k not in ("screen", "bc_mask")})
    op = real(prob_, *args, **kw)

    def apply(v):
        y = op(v)
        rank = jax.lax.axis_index("ranks")
        jax.debug.callback(
            lambda r, v_, y_: seen.setdefault((int(r), np.asarray(v_).tobytes()), np.asarray(y_)),
            rank, v, y)
        return y

    return apply


for options in ({}, {"two_phase": True, "exchange": "crystal"}):
    seen, calls = {}, []
    dist._rank_operator = spy
    solve = dist_solver(prob, mesh, n_iter=3, **options)
    jax.block_until_ready(solve(b))
    jax.jit(solve.operator).lower(b, *solve.operator_operands)  # traced: the spy sees it
    dist._rank_operator = real
    assert len(calls) == 2 and calls[0] == calls[1], calls
    assert calls[0]["two_phase"] == options.get("two_phase", False)
    want = np.asarray(operator_of(solve)(b))
    bh = np.asarray(b)
    for r in range(4):
        got = seen[(r, bh[r].tobytes())]   # the solve's first A p, p = b
        assert np.array_equal(got, want[r]), (options, r, np.abs(got - want[r]).max())
    # dist_cg runs the same program: its solution equals the solver's
    run = dist_cg(prob, mesh, b, n_iter=3, **options)
    assert np.array_equal(np.asarray(jax.jit(run)()[0]), np.asarray(solve(b)[0]))
print("OK")
""",
        devices=4,
    )


def test_box_global_indices_round_trip():
    """Global vector -> boxes -> global gives the vector back; replicas
    hold the same global index, and every global DOF is in some box."""
    run_subprocess(
        PRELUDE
        + """
for n, local in ((3, (2, 3, 2)), (7, (1, 2, 1))):
    prob, ref, idx = setup(n, local)
    assert idx.shape == (grid.size, prob.m3)
    assert sorted(set(idx.ravel().tolist())) == list(range(ref.n_global))
    xg = np.random.default_rng(2).standard_normal(ref.n_global)
    back = np.full(ref.n_global, np.nan)
    back[idx.ravel()] = xg[idx].ravel()
    assert np.array_equal(back, xg)
print("OK")
""",
        devices=4,
    )


def test_dist_setup_spans_and_tallies():
    """build_dist_problem, the plan resolution and the operator builds are
    recorded under their declared span and tally names."""
    run_subprocess(
        PRELUDE
        + """
obs.reset()
prob = build_dist_problem(3, grid, (2, 2, 2), dtype=jnp.float64)
names = [s.name for s in obs.spans()]
assert names == ["setup.dist.rank_data", "setup.dist.upload",
                 "setup.build_dist_problem"], names
parents = {s.name: s.parent for s in obs.spans()}
assert parents["setup.dist.rank_data"] == "setup.build_dist_problem"
assert parents["setup.dist.upload"] == "setup.build_dist_problem"
assert parents["setup.build_dist_problem"] is None
want = {"face_sweep": {"xch.route.face_sweep": 2},
        "crystal": {"xch.route.crystal": 1, "xch.route.face_sweep": 1},
        "fused": {"xch.route.fused": 2}}
for policy, routes in want.items():
    obs.reset()
    solve = dist_solver(prob, mesh, n_iter=5, exchange=policy)
    jax.jit(solve.operator).lower(
        jnp.zeros((grid.size, prob.m3), jnp.float64), *solve.operator_operands)
    got = obs.tallies()
    # one plan and one operator build per solver; its operator adds none
    assert got == {"op.assembly.lattice": 1, **routes}, (policy, got)
    assert [s.name for s in obs.spans()] == ["setup.exchange_plan"]
    for name in got:
        assert name in obs.TALLIES
# the same problem with its halo elements in another order: not the lattice
# of its element blocks, so the solver's operator takes and segment-sums
# through l2g
perm = np.r_[np.arange(prob.halo_elems)[::-1], np.arange(prob.halo_elems, prob.e_local)]
other = dataclasses.replace(prob, l2g=prob.l2g[perm], g=prob.g[:, perm],
                            w_local=prob.w_local[:, perm])
from repro.core.distributed import _split_assemblies
assert [a.kind for a in _split_assemblies(prob)] == ["lattice"] * 2
assert _split_assemblies(other)[0].kind == "indexed"
obs.reset()
dist_solver(other, mesh, n_iter=5, exchange="face_sweep")
assert obs.tallies() == {"op.assembly.indexed": 1, **want["face_sweep"]}, obs.tallies()
for name in ("setup.build_dist_problem", "setup.dist.rank_data",
             "setup.dist.upload", "setup.exchange_plan"):
    assert name in obs.SPANS
print("OK")
""",
        devices=4,
    )


def test_build_dist_problem_uploads_over_the_mesh():
    """With ``mesh`` each rank's arrays are uploaded to that rank's device
    alone, the solver places them without a copy, and the solve returns
    what the problem uploaded to one device gives, bit for bit."""
    run_subprocess(
        PRELUDE
        + """
one = build_dist_problem(3, grid, (2, 2, 2), lam=0.7, dtype=jnp.float64, bc="mixed")
laid = build_dist_problem(3, grid, (2, 2, 2), lam=0.7, dtype=jnp.float64, bc="mixed",
                          mesh=mesh)
for name in ("g", "w_local", "mask", "bc_mask"):
    a, b = getattr(one, name), getattr(laid, name)
    assert b.sharding == boxes, (name, b.sharding)
    assert [s.data.shape[0] for s in b.addressable_shards] == [1] * 4, name
    assert {s.device for s in b.addressable_shards} == set(mesh.devices.flat)
    assert np.array_equal(np.asarray(a), np.asarray(b)), name
solve = dist_solver(laid, mesh, n_iter=7)
assert solve.operands[0] is laid.g and solve.operands[1] is laid.w_local
assert solve.operands[2] is laid.mask
bg = np.random.default_rng(4).standard_normal(one.n_global)
rhs = to_boxes(bg, box_global_indices(one)) * laid.bc_mask
for got, want in zip(solve(rhs), dist_solver(one, mesh, n_iter=7)(rhs)):
    assert np.array_equal(np.asarray(got), np.asarray(want))
print("OK")
""",
        devices=4,
    )
