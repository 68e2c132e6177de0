"""Compile the main-path Pallas kernels natively for a described TPU v5e.

The operator's lattice Z and Z^T (XLA, no kernel) are compiled here too.

Nothing runs: each test lowers a kernel with ``interpret=False`` at the
deployment shapes of the hipBone presets and compiles it for one chip of
a described ``v5e:2x2`` topology, so what Mosaic refuses (lane-splitting
reshapes, tiles that are not (8, 128)-aligned, illegal SMEM blocks)
fails here at no chip time.  The topology is described inside a fixture:
the TPU library may be loaded by one process at a time, and describing
it while a module is imported would give pytest-xdist workers different
test lists.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.gather_scatter import lattice_gather, lattice_scatter
from repro.kernels import ops

N7, N7_LARGE = 57**3, 113**3  # n_global of hipbone_n7 / hipbone_n7_large
BATCH = 16  # hipbone_n7_batched


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, jnp.dtype(dt), sharding=sharding)
        for s, dt in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize(
    "n_degree,n_elements", [(3, 16**3), (7, 16**3), (15, 8**3)]
)
def test_element_kernel_compiles(one_chip, n_degree, n_elements):
    p = (n_degree + 1) ** 3
    n1 = n_degree + 1
    fn = lambda u, g, w, d: ops.poisson_local(
        u, g, w, d, lam=1.0, interpret=False
    )
    _compile(
        fn, one_chip,
        ((n_elements, p), "float32"),
        ((n_elements, 6, p), "float32"),
        ((n_elements, p), "float32"),
        ((n1, n1), "float32"),
    )


@pytest.mark.parametrize("n_degree,shape", [(7, (16, 16, 16)), (15, (8, 8, 8))])
def test_lattice_pair_compiles(one_chip, n_degree, shape):
    ex, ey, ez = shape
    n_global = (ex * n_degree + 1) * (ey * n_degree + 1) * (ez * n_degree + 1)
    local = (ex * ey * ez, (n_degree + 1) ** 3)
    for fn, arg in (
        (lambda x: lattice_scatter(x, shape, n_degree), (n_global,)),
        (lambda y: lattice_gather(y, shape, n_degree), local),
    ):
        hlo = jax.jit(fn).lower(
            jax.ShapeDtypeStruct(arg, jnp.float32, sharding=one_chip)
        ).compile().as_text()
        assert " gather(" not in hlo and " scatter(" not in hlo


@pytest.mark.parametrize("n", [N7, N7_LARGE])
def test_stream_stages_compile(one_chip, n):
    vec = ((n,), "float32")
    scalar = ((), "float32")
    stages = [
        (lambda r, ap, a: ops.fused_axpy_dot(r, ap, a, interpret=False),
         (vec, vec, scalar)),
        (lambda r, p, b: ops.fused_xpay(r, p, b, interpret=False),
         (vec, vec, scalar)),
        (lambda w, a, b: ops.weighted_dot(w, a, b, interpret=False),
         (vec, vec, vec)),
        (lambda d, r: ops.fused_jacobi_dot(d, r, interpret=False),
         (vec, vec)),
        (lambda a, c, d, r: ops.fused_cheb_d_update(
            a, c, d, r, interpret=False), (scalar, scalar, vec, vec)),
    ]
    for fn, shapes in stages:
        _compile(fn, one_chip, *shapes)


def test_stream_stage_vmapped_compiles(one_chip):
    """The engine's per-column stage, batched by batched_cg_assembled's vmap."""
    fn = jax.vmap(lambda r, ap, a: ops.fused_axpy_dot(r, ap, a, interpret=False))
    blk = ((BATCH, N7), "float32")
    _compile(fn, one_chip, blk, blk, ((BATCH,), "float32"))


def test_batched_stages_compile(one_chip):
    blk = ((BATCH, N7), "float32")
    col = ((BATCH,), "float32")
    _compile(
        lambda r, ap, a: ops.fused_axpy_dot_batched(r, ap, a, interpret=False),
        one_chip, blk, blk, col,
    )
    _compile(
        lambda r, p, b: ops.fused_xpay_batched(r, p, b, interpret=False),
        one_chip, blk, blk, col,
    )
    _compile(
        lambda d, r: ops.fused_jacobi_dot_batched(d, r, interpret=False),
        one_chip, ((N7,), "float32"), blk,
    )


@pytest.mark.parametrize("p", [125, 27])
def test_block_matvec_compiles(one_chip, p):
    """Materialized-Galerkin coarse apply at the N=7 ladder's N=4 and N=2
    levels (8^3 elements)."""
    e = 8**3
    _compile(
        lambda b, u: ops.block_matvec(b, u, interpret=False),
        one_chip, ((e, p, p), "float32"), ((e, p), "float32"),
    )


@pytest.mark.parametrize("x64", [False, True])
def test_kernels_compile_either_x64_setting(one_chip, x64):
    """Native kernels trace with 32-bit defaults, so a process that runs
    with jax_enable_x64 (the fp64 outer solves) still compiles them."""
    vec = ((N7,), "float32")
    with jax.enable_x64(x64):
        _compile(
            lambda r, ap, a: ops.fused_axpy_dot(r, ap, a, interpret=False),
            one_chip, vec, vec, ((), "float32"),
        )
        _compile(
            lambda b, u: ops.block_matvec(b, u, interpret=False),
            one_chip, ((512, 27, 27), "float32"), ((512, 27), "float32"),
        )
