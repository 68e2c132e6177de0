"""Quickstart: solve a screened Poisson problem with hipBone-in-JAX.

Runs the single-device benchmark in both storage modes and prints the FOM,
reproducing the paper's core comparison in ~a minute on CPU:

    PYTHONPATH=src python examples/quickstart.py [--n 7] [--elems 6]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (
    build_problem,
    cg_assembled,
    cg_scattered,
    fom,
    poisson_assembled,
    poisson_scattered,
)
from repro.core.gather_scatter import gather, scatter
from repro.kernels import ops


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=7, help="polynomial degree")
    ap.add_argument("--elems", type=int, default=6, help="elements per axis")
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--pallas", action="store_true", help="use the Pallas element kernel (native on TPU, interpreted elsewhere)")
    args = ap.parse_args()
    enable_compile_cache()

    prob = build_problem(args.n, (args.elems,) * 3, lam=1.0, dtype=jnp.float32)
    e = prob.mesh.n_elements
    print(f"mesh: {args.elems}^3 elements, N={args.n}  "
          f"N_G={prob.n_global:,} DOFs, N_L={prob.n_local:,} local nodes")

    local_op = ops.make_local_op() if args.pallas else None
    a = poisson_assembled(prob, local_op=local_op)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(prob.n_global), jnp.float32)

    solve = jax.jit(lambda b: cg_assembled(a, b, n_iter=args.iters))
    res = solve(b)
    jax.block_until_ready(res.x)
    t0 = time.perf_counter()
    res = solve(b)
    jax.block_until_ready(res.x)
    dt = time.perf_counter() - t0
    gflops = fom.fom_gflops(e, args.n, args.iters, dt)
    print(f"hipBone (assembled): {dt:.3f}s for {args.iters} CG iters "
          f"-> FOM {gflops:.2f} GFLOPS, final r.r = {float(res.rdotr):.3e}")

    a_s = poisson_scattered(prob)
    b_l = scatter(b, prob.l2g)
    solve_s = jax.jit(
        lambda bl: cg_scattered(a_s, bl, prob.w_local, n_iter=args.iters)
    )
    res_s = solve_s(b_l)
    jax.block_until_ready(res_s.x)
    t0 = time.perf_counter()
    res_s = solve_s(b_l)
    jax.block_until_ready(res_s.x)
    dt_s = time.perf_counter() - t0
    print(f"NekBone (scattered, baseline): {dt_s:.3f}s "
          f"-> FOM {fom.fom_gflops(e, args.n, args.iters, dt_s):.2f} GFLOPS")
    print(f"assembled-storage speedup: {dt_s/dt:.2f}x "
          f"(modeled byte ratio {fom.nekbone_iter_bytes(e, args.n, word=4)/fom.cg_iter_bytes(e, args.n, word=4):.2f}x)")

    # solutions agree
    xg = gather(prob.w_local * res_s.x, prob.l2g, prob.n_global)
    err = float(jnp.max(jnp.abs(xg - res.x)))
    print(f"storage-mode solution agreement: max|dx| = {err:.2e}")


if __name__ == "__main__":
    main()
