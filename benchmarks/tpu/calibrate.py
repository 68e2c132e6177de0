"""Readings that set a cell's correctness limits, taken on the chip.

    python3 benchmarks/tpu/calibrate.py --workload nekbone_n7.fixed100 \
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22 --control-seeds 31,32,33

One process: the cell's driver is built once and then driven, seed by
seed, through the same compiled solve a run times, for as many solves per
seed as a run compares (the traffic's ``check_sample``).  Each seed's
readings are the numbers a run compares (``run.judge``: the true residual
and the residual gap, each the largest over the seed's solves); the lower
reading of a limit is the largest over the program's seeds.  The control
is the plain reference in the program's place, one precision step down
(``reference.control_solve``, contractions at ``high``), on the same kind
of right-hand sides, judged by the same ``run.judge`` against the cell's
limits; its smallest reading of each number is the upper one.  The
reference at the stated ``highest`` is read beside it as a witness, and
the relative error of one operator apply at each precision beside that.
The benchmark's own runs never run this.  Prints one JSON line, also
written to ``chiprun_out/calibrate_<workload>.json`` under the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def control_samples(ref, mk, kd, *, n_iter, tol, precision, solves):
    """``(b, x, reported ||r||)`` of the reference CG at ``precision``."""
    from reference import control_solve

    solve = control_solve(ref, n_iter=n_iter, tol=tol, precision=precision)
    for i in range(solves):
        b = mk(kd, i)
        x, _, rr = solve(b)
        yield np.asarray(b, np.float64), np.asarray(x, np.float64), float(rr) ** 0.5


def apply_error(ref, x, precision: str) -> float:
    """||A_precision x - A x|| / ||A x||, the device apply against float64."""
    import jax

    y = np.asarray(jax.jit(ref.device_apply(precision))(x), np.float64)
    exact = ref.apply(np.asarray(x, np.float64))
    return float(np.linalg.norm(y - exact) / np.linalg.norm(exact))


def values(checks: dict) -> dict:
    return {name: c["value"] for name, c in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="program seeds, comma-separated")
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(CHECKOUT, "src")]
    import jax
    import jax.numpy as jnp

    import rhs
    import run
    from reference import Reference
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = run.Bench()
    w = bench.workload(args.workload)
    config, traffic = bench.config(w["config"]), bench.traffic(w["traffic"])
    limits = bench.limits(args.workload)
    devices = run._platform_check(w["chips"])[: w["chips"]]
    seeds = [int(s) for s in args.seeds.split(",")]
    cseeds = [int(s) for s in args.control_seeds.split(",")]
    per_seed = traffic["check_sample"]
    ok = set(traffic["ok_status"])

    t0 = time.perf_counter()
    driver = bench.driver(config["driver"]).build(config, traffic, seeds[0], devices)
    ref = Reference(driver.degree, driver.global_elems, driver.lam)
    program, iters = {}, {}
    for s in seeds:
        driver.reseed(s)
        samples, failed = [], 0
        for i in range(per_seed):
            out = driver.solve(i)
            driver.block(out)
            it, status, rnorm = driver.stats(out)
            iters.setdefault(s, []).append([it, status])
            failed += status not in ok
            samples.append((driver.rhs(i), driver.answer(out), rnorm))
        checks = run.judge(ref, samples, limits)
        checks["failed_solves"] = {"value": failed, "limit": 0}
        program[s] = {"values": values(checks), "correct": run.passes(checks)}
        print(f"program seed {s}: {program[s]} iterations {iters[s]}", flush=True)
    del driver, samples
    mk = jax.jit(rhs.normal_fn(ref.n_global))
    kw = dict(n_iter=traffic["n_iter"], tol=traffic["tol"], solves=per_seed)
    errors = {p: apply_error(ref, mk(jnp.asarray(rhs.key_data(cseeds[0])), 0), p)
              for p in ("highest", "high")}
    print(f"apply error by precision: {errors}", flush=True)
    control = {"highest": {}, "high": {}}
    for s in cseeds:
        kd = jnp.asarray(rhs.key_data(s))
        for p in control:
            checks = run.judge(ref, control_samples(ref, mk, kd, precision=p, **kw), limits)
            control[p][s] = {"values": values(checks), "correct": run.passes(checks)}
            print(f"reference at {p} seed {s}: {control[p][s]}", flush=True)
    numbers = run.CHECKS
    lower = {n: max(r["values"][n] for r in program.values()) for n in numbers}
    upper = {n: min(r["values"][n] for r in control["high"].values()) for n in numbers}
    out = {"workload": args.workload, "limits": limits, "solves_per_seed": per_seed,
           "lower": lower, "upper": upper,
           "program": program, "iterations": iters, "reference": control,
           "apply_error": errors, "seconds": time.perf_counter() - t0,
           "device": devices[0].device_kind}
    path = os.path.join(CHECKOUT, "chiprun_out", f"calibrate_{args.workload}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
