"""Multi-device communication tests (8 fake CPU devices via subprocess)."""

from conftest import run_subprocess


def test_exchange_algorithms_equivalent():
    run_subprocess(
        """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.compat import make_mesh, shard_map
from repro.comms.exchange import EXCHANGES
mesh = make_mesh((8,), ("r",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((64, 5)), jnp.float32)
outs = {}
for name, fn in EXCHANGES.items():
    f = jax.jit(shard_map(partial(fn, axis_name="r"), mesh=mesh,
                              in_specs=P("r"), out_specs=P("r")))
    outs[name] = np.array(f(x))
for name, o in outs.items():
    assert np.array_equal(o, outs["all_to_all"]), name
print("OK")
"""
    )


def test_crystal_router_message_count():
    """log2(P) ppermutes for crystal router vs P-1 for pairwise (HLO check)."""
    run_subprocess(
        """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.compat import make_mesh, shard_map
from repro.comms.exchange import exchange_crystal_router, exchange_pairwise
mesh = make_mesh((8,), ("r",))
x = jnp.zeros((64, 4), jnp.float32)
def count(fn):
    f = jax.jit(shard_map(partial(fn, axis_name="r"), mesh=mesh,
                              in_specs=P("r"), out_specs=P("r")))
    return f.lower(x).as_text().count("collective_permute")
c = count(exchange_crystal_router)
p = count(exchange_pairwise)
assert c == 3, c     # log2(8)
assert p == 7, p     # P-1
print("OK", c, p)
"""
    )


def test_halo_sum_and_copy_exchange():
    run_subprocess(
        """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.comms.topology import ProcessGrid
from repro.comms.halo import sum_exchange, copy_exchange
from repro.compat import make_mesh, shard_map
grid = ProcessGrid((2, 2, 2))
mesh = make_mesh((8,), ("ranks",))
mx = my = mz = 3   # per-rank box, [z,y,x] indexed
rng = np.random.default_rng(0)
boxes = rng.standard_normal((8, mz, my, mx)).astype(np.float32)

@partial(shard_map, mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"))
def do_sum(b):
    return sum_exchange(b[0], grid, "ranks")[None]

out = np.array(do_sum(jnp.asarray(boxes)))
# verify: assemble global field (2 ranks per dim, overlap of 1 plane)
G = 2 * (mx - 1) + 1
glob = np.zeros((G, G, G))
for r in range(8):
    ci, cj, ck = grid.coords(r)
    glob[ck*2:ck*2+3, cj*2:cj*2+3, ci*2:ci*2+3] += boxes[r]
for r in range(8):
    ci, cj, ck = grid.coords(r)
    want = glob[ck*2:ck*2+3, cj*2:cj*2+3, ci*2:ci*2+3]
    np.testing.assert_allclose(out[r], want, rtol=1e-5)
print("sum OK")

@partial(shard_map, mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"))
def do_copy(b):
    return copy_exchange(b[0], grid, "ranks")[None]
out2 = np.array(do_copy(jnp.asarray(boxes)))
# replicas (low faces) must equal the owner's (high-face) values
for r in range(8):
    ci, cj, ck = grid.coords(r)
    if ci > 0:
        left = grid.rank(ci - 1, cj, ck)
        np.testing.assert_allclose(out2[r][:, :, 0], out2[left][:, :, 2], rtol=1e-6)
print("OK")
"""
    )


def test_halo_routings_bitwise_equivalent():
    """crystal/fused halo routings == face sweep, bit for bit.

    At the native wire every routing must replicate the face sweep's IEEE
    reduction tree exactly (that is what makes ``comms.plan`` a pure
    performance knob); with a narrowed fp32 wire on fp64 boxes the sum
    routings agree to wire rounding while each stays replica-consistent.
    """
    run_subprocess(
        """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.comms.topology import ProcessGrid
from repro.comms import halo
from repro.compat import make_mesh, shard_map

mesh = make_mesh((8,), ("r",))
rng = np.random.default_rng(0)

def run(fn, boxes):
    f = jax.jit(shard_map(lambda b: fn(b[0])[None], mesh=mesh,
                          in_specs=P("r"), out_specs=P("r")))
    return np.array(f(jnp.asarray(boxes)))

for shape in [(2, 2, 2), (4, 2, 1), (8, 1, 1)]:
    grid = ProcessGrid(shape)
    for box_shape, dt in [((3, 4, 5), np.float64), ((3, 3, 3), np.float32)]:
        boxes = rng.standard_normal((8, *box_shape)).astype(dt)
        for wire in (None, jnp.float32):
            bitwise = wire is None or dt == np.float32
            ref = run(lambda b: halo.sum_exchange(b, grid, "r", wire), boxes)
            for routing in ("crystal", "fused"):
                got = run(lambda b: halo.sum_exchange(
                    b, grid, "r", wire, routing), boxes)
                if bitwise:
                    assert np.array_equal(ref, got), (shape, routing, wire)
                else:
                    assert np.allclose(ref, got, rtol=1e-6, atol=1e-6)
            refc = run(lambda b: halo.copy_exchange(b, grid, "r", wire), boxes)
            gotc = run(lambda b: halo.copy_exchange(
                b, grid, "r", wire, "fused"), boxes)
            # copy ships owner values verbatim: bitwise at every wire
            assert np.array_equal(refc, gotc), (shape, wire)
            depth = 1
            refe = run(lambda b: halo.expand_exchange(
                b, grid, "r", depth, wire), boxes)
            gote = run(lambda b: halo.expand_exchange(
                b, grid, "r", depth, wire, "fused"), boxes)
            assert np.array_equal(refe, gote), (shape, wire)
            big = rng.standard_normal(
                (8, *(s + 2 * depth for s in box_shape))).astype(dt)
            refk = run(lambda b: halo.contract_exchange(
                b, grid, "r", depth, wire), big)
            gotk = run(lambda b: halo.contract_exchange(
                b, grid, "r", depth, wire, "fused"), big)
            if bitwise:
                assert np.array_equal(refk, gotk), (shape, wire)
            else:
                assert np.allclose(refk, gotk, rtol=1e-5, atol=1e-5)
print("OK")
""",
        timeout=900,
    )


def test_distributed_cg_matches_single_device():
    run_subprocess(
        """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core.distributed import build_dist_problem, dist_cg, dist_cg_scattered
from repro.comms.topology import ProcessGrid
from repro.core import build_problem, poisson_assembled, cg_assembled
from repro.compat import make_mesh

N = 3
grid = ProcessGrid((2, 2, 2)); local = (2, 1, 1)
gshape = (4, 2, 2)
ref = build_problem(N, gshape, lam=0.8, dtype=jnp.float64)
A = poisson_assembled(ref)
mesh = make_mesh((8,), ("ranks",))
prob = build_dist_problem(N, grid, local, lam=0.8, dtype=jnp.float64)
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
x_boxes, rdotr, iters, status, hist = jax.jit(dist_cg(prob, mesh, b_boxes, n_iter=150))()
res = cg_assembled(A, jnp.asarray(bg), n_iter=150)
err = np.abs(np.array(x_boxes) - box_from_global(np.array(res.x))).max()
assert err < 1e-9, err
# scattered baseline
bL = jnp.take(b_boxes, jnp.asarray(prob.l2g.reshape(-1)), axis=1).reshape(
    grid.size, prob.e_local, -1)
xl, rd2, _it, _st = jax.jit(dist_cg_scattered(prob, mesh, bL, n_iter=150))()
xl_ref = jnp.take(jnp.asarray(box_from_global(np.array(res.x))),
                  jnp.asarray(prob.l2g.reshape(-1)), axis=1).reshape(xl.shape)
assert np.abs(np.array(xl) - np.array(xl_ref)).max() < 1e-9
print("OK")
"""
    )


def test_two_phase_matches_one_phase():
    """Paper-faithful two-phase operator == merged one-phase operator."""
    run_subprocess(
        """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core.distributed import build_dist_problem, _on_rank, _rank_operator
from repro.comms.topology import ProcessGrid
from repro.core.operator import local_poisson

from repro.compat import make_mesh, shard_map
grid = ProcessGrid((2, 2, 1)); local = (1, 1, 2)
mesh = make_mesh((4,), ("ranks",))
prob = build_dist_problem(2, grid, local, lam=0.5, dtype=jnp.float64)
rng = np.random.default_rng(0)
x = rng.standard_normal((4, prob.m3))
# make consistent: copy owners into replicas by reusing copy_exchange
from repro.comms.halo import copy_exchange
spec = P("ranks")
@partial(shard_map, mesh=mesh, in_specs=(spec,)*3, out_specs=(spec, spec))
def apply_both(xb, g, w):
    xc = copy_exchange(xb[0].reshape(prob.box_shape[::-1]), prob.grid, "ranks").reshape(-1)
    rank = _on_rank(prob, g[0], w[0], None)
    one = _rank_operator(rank, local_op=local_poisson, two_phase=False)(xc)
    two = _rank_operator(rank, local_op=local_poisson, two_phase=True)(xc)
    return one[None], two[None]
one, two = apply_both(jnp.asarray(x), prob.g, prob.w_local)
np.testing.assert_allclose(np.array(one), np.array(two), atol=1e-11)
print("OK")
"""
    )


def test_compressed_psum_error_feedback():
    run_subprocess(
        """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.training.compress import compressed_psum, ef_compressed_psum
from repro.compat import make_mesh, shard_map
mesh = make_mesh((8,), ("r",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)

@partial(shard_map, mesh=mesh, in_specs=P("r"), out_specs=P("r"))
def f(xs):
    return compressed_psum(xs[0], "r")[None]
got = np.array(f(x))[0]
want = np.array(x).sum(0)
# int8 quantization error bounded
assert np.abs(got - want).max() < 8 * np.abs(x).max() / 127 + 1e-5

# error feedback: mean of compressed psums over steps converges to true sum
@partial(shard_map, mesh=mesh, in_specs=(P("r"), P("r")), out_specs=(P("r"), P("r")))
def g(xs, res):
    t, r = ef_compressed_psum(xs[0], res[0], "r")
    return t[None], r[None]
res = jnp.zeros_like(x)
acc = np.zeros(256)
steps = 20
for _ in range(steps):
    t, res = g(x, res)
    acc += np.array(t)[0]
err_ef = np.abs(acc / steps - want).max()
assert err_ef < np.abs(got - want).max() + 1e-5  # EF at least as good
print("OK", err_ef)
"""
    )
