"""Solver-service driver: ``python -m repro.launch.serve --config <id>``.

Feeds the :class:`repro.serving.SolverEngine` two rounds of multi-RHS
solve requests from a ``PoissonConfig`` spec: the first round pays the
one-time setup (cache miss), the second reuses it (cache hit, zero
preconditioner setup) — the amortization profile the batched-solve
benchmark measures.  Prints per-column iterations/status and the cache
counters; exits nonzero if any column fails to converge or the second
round misses the cache.

The seed's LM decode driver lives on as ``examples/serve_lm.py``
(``repro.serving.lm``).
"""
import argparse
import sys

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.hipbone import CONFIGS, REDUCED
from repro.core import build_problem
from repro.serving import SolveRequest, SolverEngine, SolverServeConfig


def serve_rounds(
    cfg,
    *,
    requests: int | None = None,
    max_batch: int = 16,
    rounds: int = 2,
    seed: int = 0,
) -> tuple[SolverEngine, int]:
    """Feed ``rounds`` rounds of random-RHS requests for ``cfg`` through
    the engine; print each round; return the engine and the failure count
    (unconverged columns plus repeated rounds that missed the setup cache).
    """
    n_req = requests or max(cfg.batch_rhs, 1)
    prob = build_problem(
        cfg.n_degree, cfg.local_elems, lam=cfg.lam,
        dtype=jnp.dtype(cfg.dtype), **cfg.problem_kwargs()
    )
    engine = SolverEngine(SolverServeConfig(max_batch=max_batch))
    rng = np.random.default_rng(seed)

    print(
        f"solver service: {cfg.name} N={cfg.n_degree} "
        f"dofs={prob.n_global} precond={cfg.precond} "
        f"requests={n_req}/round × {rounds} rounds"
    )
    failures = 0
    for rnd in range(rounds):
        reqs = [
            SolveRequest(
                prob=prob,
                b=jnp.asarray(
                    rng.standard_normal(prob.n_global), prob.dtype
                ),
                kind=cfg.precond,
                precond=cfg.precond_kwargs(),
                tol=cfg.tol if cfg.tol is not None else 1e-6,
                n_iter=cfg.n_iter if cfg.tol is None else 500,
                cg_variant=cfg.cg_variant,
            )
            for _ in range(n_req)
        ]
        responses = engine.solve(reqs)
        iters = [r.iterations for r in responses]
        setup = responses[0].setup_cache
        print(
            f"round {rnd}: setup={setup} "
            f"iterations={iters} "
            f"status={[r.status_name for r in responses]}"
        )
        failures += sum(not r.converged for r in responses)
        if rnd > 0 and setup != "hit":
            print("ERROR: repeated round missed the setup cache")
            failures += 1
    print("cache:", engine.cache.stats())
    return engine, failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--config", default="hipbone_reduced",
        choices=sorted(CONFIGS) + ["hipbone_reduced"],
    )
    ap.add_argument("--requests", type=int, default=None,
                    help="RHS columns per round (default: config batch_rhs)")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="engine slot width per dispatch")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = REDUCED if args.config == "hipbone_reduced" else CONFIGS[args.config]
    _, failures = serve_rounds(
        cfg, requests=args.requests, max_batch=args.max_batch,
        rounds=args.rounds, seed=args.seed,
    )
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
