"""Smoke test of the hipBone solve path on a TPU: the quickest proof it runs.

    python chip_smoke.py              # one chip, phases (a)-(e)
    python chip_smoke.py --chips 4    # the sharded path on a 2x2 host only

One process, no children: a chip belongs to the process that touched JAX
first.  Every phase goes through the entry points users call
(``build_problem``, ``poisson_assembled``, ``cg_assembled``,
``make_preconditioner``, ``SolverEngine`` as ``launch/serve.py`` drives it,
``dist_cg``) at deployment size, checks its answer, and lets any failure
propagate: the script then exits non-zero.  Without a TPU it prints
``{"ok": false, ...}`` and exits 1 without running anything.

Phases on one chip:
  (a) the device: platform, kind, count;
  (b) NekBone mode, 100 fixed fp32 CG iterations on hipbone_n7_large and
      hipbone_n15_large: operator and element-kernel parity with the pure
      jnp reference (rel. err <= 1e-5), the policy's operator choice, and
      the same solve with the Pallas element kernel and stream stage;
  (c) the solver engine on hipbone_n7_batched: two rounds of 16 requests,
      fused stream stages on, every column converged, round 2 a cache hit;
  (d) pMG with materialized Galerkin coarse operators at hipbone_n7 size:
      the Pallas block matvec takes the einsum's iteration count;
  (e) mixed precision, hipbone_n7_pmg_fp32: fp64 outer loop, fp32 chain.
With ``--chips 4``: ``dist_solver`` (``dist_cg``'s solve) on a factor3(4)
grid with hipbone_n7 elements per chip, NekBone mode and pMG-galerkin_mat
to 1e-6, each against the single-device solve of the same global problem.

Every Pallas call of a checked program is listed with its ``interpret``
flag (all must be False) and operand dtypes.  Times printed here are smoke
timings of single calls, not benchmarks.  The last stdout line is the
JSON verdict.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PARITY_TOL = 1e-5  # Pallas vs pure-jnp reference, fp32 relative 2-norm
DIST_TOL = 1e-4    # sharded vs single-device solution, relative 2-norm


def _verdict(ok: bool, device: dict | None = None, **extra) -> None:
    out = {"ok": ok}
    if device is not None:
        out["device"] = device
    out.update(extra)
    print(json.dumps(out), flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def pallas_calls(fn, *args) -> list[tuple[str, bool, tuple]]:
    """(name, interpret, operand dtypes) of every pallas_call fn traces."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                found.append((
                    str(getattr(name, "name", name)),
                    bool(eqn.params["interpret"]),
                    tuple(sorted({str(v.aval.dtype) for v in eqn.invars})),
                ))
            for val in eqn.params.values():
                for sub in val if isinstance(val, (tuple, list)) else (val,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def report_pallas(calls, *, expect_some: bool) -> None:
    names = sorted({c[0] for c in calls})
    print(f"  pallas calls: {len(calls)} {names}", flush=True)
    for name, interp, dts in sorted(set(calls)):
        print(f"    {name}: interpret={interp} dtypes={list(dts)}", flush=True)
    if expect_some:
        check(bool(calls), "the checked program contains Pallas kernels")
    check(all(not c[1] for c in calls), "every Pallas call has interpret=False")
    check(
        all("float64" not in c[2] for c in calls),
        "no Pallas call is handed float64",
    )


def rel_err(got, want) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def compile_and_run(fn, *args):
    """AOT-compile fn for args, run once; (result, compile_s, run_s)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = compiled(*args)
    jax.block_until_ready(out)
    return out, t1 - t0, time.perf_counter() - t1


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def problem(name: str, dtype=None, **overrides):
    import dataclasses

    import jax.numpy as jnp

    from repro.configs.hipbone import CONFIGS
    from repro.core import build_problem

    cfg = dataclasses.replace(CONFIGS[name], **overrides)
    prob = build_problem(
        cfg.n_degree, cfg.local_elems, lam=cfg.lam,
        dtype=jnp.dtype(dtype or cfg.dtype), **cfg.problem_kwargs(),
    )
    return cfg, prob


def rhs(prob, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(prob.n_global), prob.dtype)


# ---------------------------------------------------------------- phases


def phase_nekbone(name: str, dev) -> None:
    """(b) 100 fixed CG iterations, fp32, with parity checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import cg_assembled, local_poisson, poisson_assembled
    from repro.core.operator import screen_stream
    from repro.kernels import ops

    cfg, prob = problem(name)
    print(f"(b) NekBone mode: {name} N={cfg.n_degree} "
          f"elements={prob.mesh.n_elements} dofs={prob.n_global}", flush=True)
    a_policy = poisson_assembled(prob)
    print(f"  policy operator: {'fused' if a_policy.fused else 'split'} "
          f"(local op: XLA local_poisson); "
          f"should_fuse_operator()={ops.should_fuse_operator()}", flush=True)
    check(not a_policy.fused, "policy picks the split operator on the chip")
    a_ref = poisson_assembled(prob, fused=False)
    a_pallas = poisson_assembled(prob, local_op=ops.make_local_op())

    x = rhs(prob, seed=1)
    y_ref = jax.jit(a_ref)(x)
    y_pl = jax.jit(a_pallas)(x)
    err = rel_err(y_pl, y_ref)
    print(f"  operator apply, Pallas element kernel vs jnp split: "
          f"rel err {err:.3e}", flush=True)
    check(err <= PARITY_TOL, f"operator parity <= {PARITY_TOL}")

    w_eff, lam_eff = screen_stream(prob)
    rng = np.random.default_rng(2)
    u = jnp.asarray(
        rng.standard_normal((prob.mesh.n_elements, prob.mesh.points_per_element)),
        jnp.float32,
    )
    local_pl = lambda u: ops.poisson_local(u, prob.g, w_eff, prob.d, lam=lam_eff)
    want = jax.jit(lambda u: local_poisson(u, prob.g, prob.d, lam_eff, w_eff))(u)
    err = rel_err(jax.jit(local_pl)(u), want)
    print(f"  element kernel ops.poisson_local vs local_poisson: "
          f"rel err {err:.3e}", flush=True)
    check(err <= PARITY_TOL, f"element-kernel parity <= {PARITY_TOL}")
    report_pallas(pallas_calls(local_pl, u), expect_some=True)

    b = rhs(prob)
    rr0 = float(jnp.vdot(b, b))
    solves = {
        "policy (XLA split)": lambda b: cg_assembled(a_policy, b, n_iter=100),
        "Pallas element kernel + fused stream stage": lambda b: cg_assembled(
            a_pallas, b, n_iter=100, fused_update=ops.fused_axpy_dot
        ),
    }
    for label, fn in solves.items():
        res, c_s, r_s = compile_and_run(fn, b)
        rr = float(res.rdotr)
        print(f"  solve [{label}]: {int(res.iterations)} iterations, "
              f"r.r {rr0:.3e} -> {rr:.3e}; compile {c_s:.2f} s, "
              f"smoke timing {r_s:.4f} s (not a benchmark)", flush=True)
        check(np.isfinite(rr) and rr < rr0, "final r.r finite and below r0.r0")
    report_pallas(pallas_calls(solves["Pallas element kernel + fused stream stage"], b),
                  expect_some=True)
    print(f"  peak_bytes_in_use: {peak_bytes(dev)}", flush=True)


def phase_engine(dev) -> None:
    """(c) SolverEngine on hipbone_n7_batched, as launch/serve.py drives it."""
    import jax.numpy as jnp

    from repro.configs.hipbone import CONFIGS
    from repro.core import batched_cg_assembled
    from repro.kernels import ops
    from repro.launch.serve import serve_rounds

    cfg = CONFIGS["hipbone_n7_batched"]
    print(f"(c) engine: {cfg.name} B={cfg.batch_rhs}", flush=True)
    check(ops.should_fuse_streams(jnp.float32), "fused stream stages are on")
    t0 = time.perf_counter()
    engine, failures = serve_rounds(cfg, rounds=2)
    print(f"  smoke timing, two rounds: {time.perf_counter() - t0:.2f} s "
          f"(round 0 includes setup and compile; not a benchmark)", flush=True)
    for rec in engine.records:
        print(f"  dispatch: batch={rec['batch']} setup={rec['setup_cache']} "
              f"solve_s={rec['solve_s']:.3f} max_iter={max(rec['iterations'])}",
              flush=True)
    check(failures == 0, "every column converged and round 2 hit the setup cache")
    check([r["setup_cache"] for r in engine.records] == ["miss", "hit"],
          "setup cache: miss then hit")
    # the program a dispatch runs, traced with the engine's own kwargs
    from repro.core import build_problem
    from repro.serving import SolveRequest

    prob = build_problem(cfg.n_degree, cfg.local_elems, lam=cfg.lam,
                         dtype=jnp.float32, **cfg.problem_kwargs())
    req = SolveRequest(prob=prob, b=rhs(prob), kind=cfg.precond,
                       precond=cfg.precond_kwargs(), tol=cfg.tol, n_iter=500)
    setup = engine.cache.get_or_build(prob, req.kind, **dict(req.precond))
    block = jnp.stack([req.b] * cfg.batch_rhs)
    report_pallas(pallas_calls(
        lambda blk: batched_cg_assembled(
            setup.operator, blk, **engine._cg_kwargs(req, setup)
        ), block,
    ), expect_some=True)
    print(f"  peak_bytes_in_use: {peak_bytes(dev)}", flush=True)


def phase_pmg(dev) -> None:
    """(d) pMG with Galerkin blocks: Pallas block matvec vs einsum."""
    from repro.core import cg_assembled, poisson_assembled
    from repro.core.precond import make_preconditioner
    from repro.kernels import ops

    cfg, prob = problem("hipbone_n7_pmg", pmg_coarse_op="galerkin_mat")
    print(f"(d) pMG galerkin_mat at hipbone_n7 size: dofs={prob.n_global} "
          f"tol={cfg.tol}", flush=True)
    a = poisson_assembled(prob)
    b = rhs(prob)
    iters = {}
    for label, matvec in (("einsum", None), ("Pallas", ops.make_block_matvec())):
        pc, _ = make_preconditioner(
            "pmg", prob, a, pmg_coarse_op="galerkin_mat", galerkin_matvec=matvec
        )
        fn = lambda b, pc=pc: cg_assembled(a, b, n_iter=500, tol=cfg.tol,
                                           precond=pc)
        res, c_s, r_s = compile_and_run(fn, b)
        iters[label] = int(res.iterations)
        print(f"  {label} block matvec: {iters[label]} iterations, status "
              f"{int(res.status)}; compile {c_s:.2f} s, smoke timing "
              f"{r_s:.4f} s (not a benchmark)", flush=True)
        check(int(res.status) == 0, f"{label} solve converged")
        if matvec is not None:
            report_pallas(pallas_calls(fn, b), expect_some=True)
    check(iters["Pallas"] == iters["einsum"],
          "Pallas block matvec takes the einsum's iteration count")
    print(f"  peak_bytes_in_use: {peak_bytes(dev)}", flush=True)


def phase_mixed(dev) -> None:
    """(e) fp64 outer PCG with an fp32 pMG chain, to 1e-8."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    from repro.core import cg_assembled, poisson_assembled
    from repro.core.precond import make_preconditioner
    from repro.kernels import ops

    cfg, prob = problem("hipbone_n7_pmg_fp32")
    print(f"(e) mixed precision: {cfg.name} dtype={cfg.dtype} "
          f"chain={cfg.precond_dtype} tol={cfg.tol}", flush=True)
    check(not ops.should_fuse_streams(jnp.float64),
          "no fused stream stage for float64")
    a = poisson_assembled(prob)
    check(not a.fused, "float64 operator stays on the XLA split path")
    pc, info = make_preconditioner("pmg", prob, a, **cfg.precond_kwargs())
    b = rhs(prob)
    check(b.dtype == jnp.float64, "outer loop runs in float64")
    fn = lambda b: cg_assembled(a, b, n_iter=500, tol=cfg.tol, precond=pc,
                                cg_variant=cfg.cg_variant)
    res, c_s, r_s = compile_and_run(fn, b)
    print(f"  {int(res.iterations)} iterations, status {int(res.status)}, "
          f"chain dtype {info.dtype}; compile {c_s:.2f} s, smoke timing "
          f"{r_s:.4f} s (not a benchmark)", flush=True)
    check(int(res.status) == 0, "mixed-precision solve converged to 1e-8")
    report_pallas(pallas_calls(fn, b), expect_some=False)
    print(f"  peak_bytes_in_use: {peak_bytes(dev)}", flush=True)


def phase_sharded(devices) -> None:
    """--chips 4: dist_solver on 4 chips vs the single-device solve."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comms.topology import ProcessGrid, factor3
    from repro.compat import make_mesh
    from repro.configs.hipbone import CONFIGS
    from repro.core import build_problem, cg_assembled, poisson_assembled
    from repro.core.distributed import (
        box_global_indices,
        build_dist_problem,
        dist_solver,
    )
    from repro.core.precond import make_preconditioner

    cfg = CONFIGS["hipbone_n7"]
    ranks = 4
    grid = ProcessGrid(factor3(ranks))
    gshape = tuple(g * e for g, e in zip(grid.shape, cfg.local_elems))
    mesh = make_mesh((ranks,), ("ranks",), devices=devices[:ranks])
    dprob = build_dist_problem(cfg.n_degree, grid, cfg.local_elems,
                               lam=cfg.lam, dtype=jnp.float32)
    ref = build_problem(cfg.n_degree, gshape, lam=cfg.lam, dtype=jnp.float32)
    idx = box_global_indices(dprob)
    print(f"(sharded) dist_solver on grid {grid.shape}, {cfg.local_elems} "
          f"elements per chip, N={cfg.n_degree}; global {gshape} "
          f"dofs={ref.n_global}", flush=True)
    bg = np.random.default_rng(0).standard_normal(ref.n_global)
    b_boxes = jax.device_put(
        jnp.asarray(bg[idx], jnp.float32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("ranks")),
    )
    a = poisson_assembled(ref)
    cases = {
        "NekBone 100 iterations": (dict(n_iter=100), None),
        "pMG galerkin_mat tol 1e-6": (
            dict(n_iter=500, tol=1e-6, precond="pmg",
                 pmg_coarse_op="galerkin_mat", pmg_coarse_iters=16),
            dict(pmg_coarse_op="galerkin_mat", pmg_coarse_solve="chebyshev",
                 pmg_coarse_iters=16),
        ),
    }
    for label, (dkw, skw) in cases.items():
        t0 = time.perf_counter()
        solve = dist_solver(dprob, mesh, **dkw)
        x_boxes, rdotr, iters, status, _ = solve(b_boxes)
        jax.block_until_ready(x_boxes)
        d_s = time.perf_counter() - t0
        shard_devs = {s.device for s in x_boxes.addressable_shards}
        pc = None if skw is None else make_preconditioner("pmg", ref, a, **skw)[0]
        res = jax.jit(lambda b, pc=pc: cg_assembled(
            a, b, n_iter=dkw["n_iter"], tol=dkw.get("tol"), precond=pc
        ))(jnp.asarray(bg, jnp.float32))
        x1 = np.asarray(res.x, np.float64)
        xd = np.zeros_like(x1)
        xd[idx.reshape(-1)] = np.asarray(x_boxes, np.float64).reshape(-1)
        err = float(np.linalg.norm(xd - x1) / np.linalg.norm(x1))
        print(f"  {label}: dist {int(iters)} iterations (status "
              f"{int(status)}), single {int(res.iterations)} (status "
              f"{int(res.status)}); ||x_dist-x_1||/||x_1|| = {err:.3e}; "
              f"dist compile+run smoke timing {d_s:.2f} s (not a benchmark)",
              flush=True)
        check(len(shard_devs) == ranks, f"x shards sit on {ranks} distinct devices")
        check(int(iters) == int(res.iterations), "iterations equal")
        check(err <= DIST_TOL, f"solution agrees to {DIST_TOL}")
        if "tol" in dkw:
            check(int(status) == 0 and int(res.status) == 0, "both converged")
    for d in devices[:ranks]:
        print(f"  {d}: peak_bytes_in_use {peak_bytes(d)}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its comparison")
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        _verdict(False, error=f"no repro package under {src}")
        return 1
    sys.path.insert(0, src)
    import jax

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"(a) device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        _verdict(False, device, error="no TPU: JAX sees only "
                 f"{dev.platform} devices")
        return 1
    if len(devices) < args.chips:
        _verdict(False, device,
                 error=f"--chips {args.chips} needs {args.chips} devices")
        return 1

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(devices)
    else:
        for name in ("hipbone_n7_large", "hipbone_n15_large"):
            phase_nekbone(name, dev)
        phase_engine(dev)
        phase_pmg(dev)
        phase_mixed(dev)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s "
          "(smoke timing, not a benchmark)", flush=True)
    _verdict(True, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
