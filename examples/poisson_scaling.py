"""Distributed hipBone: multi-rank CG with communication-hiding split.

Runs the full distributed path — padded-consistent assembled storage, halo
sum-exchange via static ppermutes, interior/halo overlap split, and
masked+psum inner products — on one rank per device.  On a TPU host the
ranks are the chips (``--ranks`` defaults to all of them; ``--ranks 1``
uses the first); elsewhere they are virtual CPU devices (default 8):

    PYTHONPATH=src python examples/poisson_scaling.py --ranks 8 --n 7
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.comms.topology import ProcessGrid, factor3
from repro.compile_cache import enable_compile_cache
from repro.core.cg import status_name
from repro.core.distributed import build_dist_problem, dist_cg, dist_spectrum
from repro.core.fom import nekbone_flops_per_iter

CPU_RANKS = 8


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=None,
                    help="ranks, one per device (default: every chip on a "
                         f"TPU host, else {CPU_RANKS} virtual CPU devices)")
    ap.add_argument("--n", type=int, default=7)
    ap.add_argument("--local", type=int, default=2, help="elements per axis per rank")
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--precond",
                    choices=["none", "jacobi", "chebyshev", "schwarz", "pmg",
                             "pmg-schwarz", "pmg-galerkin-mat"],
                    default="none", help="PCG preconditioner "
                    "(pmg-galerkin-mat = materialized P^T A P coarse "
                    "operators, the benchmark ladder's name for "
                    "pmg_coarse_op='galerkin_mat')")
    ap.add_argument("--cheb-degree", type=int, default=2)
    ap.add_argument("--tol", type=float, default=None,
                    help="stop at ||r|| <= tol*||r0|| instead of fixed iters")
    ap.add_argument("--precond-dtype", choices=["float32", "float64"],
                    default=None,
                    help="mixed precision: compute dtype of the whole "
                         "preconditioner chain (fp32 halves M⁻¹ HBM/wire "
                         "bytes inside an fp64 solve; implies --dtype "
                         "float64 makes sense)")
    ap.add_argument("--dtype", choices=["float32", "float64"],
                    default="float32", help="outer solve dtype")
    ap.add_argument("--cg-variant", choices=["standard", "flexible"],
                    default=None,
                    help="CG β recurrence; default flexible when the "
                         "preconditioner dtype is narrower than the solve")
    ap.add_argument("--two-phase", action="store_true",
                    help="paper-faithful two-phase comm (halo + gather)")
    ap.add_argument("--exchange",
                    choices=["auto", "face_sweep", "crystal", "fused"],
                    default=None,
                    help="halo-exchange routing policy (comms.plan): "
                         "'auto' times the candidates per site at setup "
                         "and picks winners; a named routing pins every "
                         "site.  Default: HIPBONE_EXCHANGE env, else auto. "
                         "Iteration counts are identical under every "
                         "choice — only wall time moves.")
    args = ap.parse_args()

    # virtual CPU devices, one per rank (read when the backend starts; a
    # TPU backend ignores the flag)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.ranks or CPU_RANKS}"
        ).strip()
    enable_compile_cache()
    devices = jax.devices()
    default_ranks = len(devices) if jax.default_backend() == "tpu" else CPU_RANKS
    ranks = args.ranks or default_ranks
    if ranks > len(devices):
        ap.error(f"--ranks {ranks} exceeds the {len(devices)} devices here")
    dtype = jnp.dtype(args.dtype)
    if dtype == jnp.float64:
        jax.config.update("jax_enable_x64", True)
    pdtype = None if args.precond_dtype is None else jnp.dtype(args.precond_dtype)
    if pdtype is not None and pdtype.itemsize > dtype.itemsize:
        ap.error(
            f"--precond-dtype {pdtype.name} is wider than --dtype "
            f"{dtype.name}; mixed precision narrows the preconditioner"
        )
    variant = args.cg_variant or (
        "flexible" if pdtype is not None and pdtype != dtype else "standard"
    )
    grid = ProcessGrid(factor3(ranks))
    mesh = make_mesh((ranks,), ("ranks",), devices=devices[:ranks])
    local = (args.local,) * 3
    prob = build_dist_problem(args.n, grid, local, lam=1.0, dtype=dtype)
    print(f"devices: {devices[0].platform} {devices[0].device_kind} x{ranks}")
    print(f"ranks={ranks} grid={grid.shape} local={local} N={args.n} "
          f"global DOFs={prob.n_global:,} halo elems/rank={prob.halo_elems}/{prob.e_local} "
          f"precond={args.precond}")

    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal((ranks, prob.m3)), dtype)
    # estimate the Chebyshev interval once at setup so the timed runs below
    # are pure solve (dist_cg would otherwise re-run the Lanczos operator
    # applies inside every compiled call); pmg estimates per level in-graph
    lmin = lmax = None
    if args.precond == "chebyshev":
        lmin, lmax = dist_spectrum(prob, mesh, two_phase=args.two_phase)
        print(f"lanczos: spectrum(D^-1 A) ~= [{lmin:.4f}, {lmax:.4f}]")
    precond, smoother, coarse_op = args.precond, "chebyshev", "redisc"
    if precond == "pmg-schwarz":
        precond, smoother = "pmg", "schwarz"
    elif precond == "pmg-galerkin-mat":
        precond, coarse_op = "pmg", "galerkin_mat"
    run = jax.jit(dist_cg(prob, mesh, b, n_iter=args.iters, tol=args.tol,
                          precond=precond, cheb_degree=args.cheb_degree,
                          pmg_smoother=smoother, pmg_coarse_op=coarse_op,
                          lmin=lmin, lmax=lmax,
                          precond_dtype=pdtype, cg_variant=variant,
                          two_phase=args.two_phase, record_history=True,
                          exchange=args.exchange))
    plan = getattr(getattr(run, "__wrapped__", run), "exchange_plan", None)
    if plan is not None:
        if plan.sites:
            for rec in plan.records():
                print(f"exchange plan: {rec['site']:>12} -> {rec['routing']}"
                      f"/{rec['wire_dtype'] or 'native'}"
                      + (" (cached)" if rec["from_cache"] else ""))
        else:
            print(f"exchange plan: policy {plan.policy!r} pinned at every site")
    x, rdotr, iters, status, hist = run()
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    x, rdotr, iters, status, hist = run()
    jax.block_until_ready(x)
    dt = time.perf_counter() - t0

    n_done = int(iters)
    print(f"status: {status_name(status)}")
    e_tot = ranks * prob.e_local
    fom = nekbone_flops_per_iter(e_tot, args.n) * n_done / dt / 1e9
    print(f"{n_done} CG iters in {dt:.3f}s -> FOM {fom:.2f} GFLOPS "
          f"({fom/ranks:.2f}/rank)  final r.r={float(rdotr):.3e}")
    h = np.asarray(hist)[:max(n_done, 1)]
    print(f"residual: {h[0]:.3e} -> {h[-1]:.3e} over {n_done} iters")


if __name__ == "__main__":
    main()
