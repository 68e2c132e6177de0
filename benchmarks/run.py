"""Benchmark driver — one section per paper table/figure.

``python -m benchmarks.run [--full]`` prints CSV rows per benchmark:
  fig3     — operator GFLOPS vs N + roofline      (paper Fig. 3)
  table1   — kernel occupancy/VMEM analogue       (paper Table 1)
  fig456   — multi-rank scaling + throughput      (paper Figs. 4-6)
  table2   — peak FOM / weak scaling / NekBone-vs-hipBone (paper Table 2)
  exchange — routing-algorithm selection          (paper §MPI Communication)
  precond  — PCG iterations-to-tolerance + FOM    (beyond the benchmark)
  batched  — multi-RHS setup amortization sweep   (beyond the benchmark)

``--only`` takes a comma-separated section list (``--only fig3,precond``).

``--json PATH`` additionally writes a machine-readable summary: every
section's raw CSV rows plus the precond sweep (``precond_records``), the
fig3 sweep (``fig3_records``), the multi-RHS amortization sweep
(``batched_records``: per-(N, kind, B) max column iterations, setup-cache
hit/miss state and per-solve wall share) and the halo-exchange plan build
(``exchange_records``: per-site candidate timings, winning routing, wire
bytes — the ``comms.plan`` autotuner over a real solver setup's site
list) as structured records.  Every record in
both carries the dry-run roofline triple ``model_bytes`` /
``achievable_s`` / ``pct_roofline`` (analytic Eq. 4–6 traffic bound over
the AOT-compiled program's own HLO roofline time at the TPU_V5E
constants — machine-independent; see roofline/bench.py), alongside the
precond sweep's per-config iterations-to-tol, solve time, effective FOM
and per-application preconditioner wall time ``precond_apply_s``.  The
perf trajectory is tracked across PRs — CI passes ``--json
BENCH_pr6.json`` (bump the name per PR) and gates on
``scripts/compare_bench.py``, which fails if any shared case needs more
iterations or loses more roofline fraction than the slack allows.  The
full json schema and gate rules are documented in docs/BENCHMARKS.md.
"""
import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="larger problem sizes")
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated section names (e.g. fig3,precond)",
    )
    ap.add_argument(
        "--json",
        default="",
        help="write a machine-readable summary to this path (off by default)",
    )
    args = ap.parse_args()
    quick = not args.full

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        batched_solve,
        exchange_select,
        fig3_operator,
        fig456_scaling,
        precond_solve,
        table1_blocks,
        table2_fom,
    )

    sections = {
        "fig3": None,  # records sections: sweep runs once, json gets dicts
        "table1": table1_blocks.main,
        "fig456": fig456_scaling.main,
        "table2": table2_fom.main,
        "exchange": None,
        "precond": None,
        "batched": None,
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(sections)
        if unknown:
            sys.exit(f"unknown section(s): {','.join(sorted(unknown))}")
    summary: dict = {"quick": quick, "sections": {}, "failures": []}
    failures = 0
    for name, fn in sections.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            if name == "precond":
                recs = precond_solve.records(quick=quick)
                rows = precond_solve.rows_from(recs)
                summary["precond_records"] = recs
            elif name == "fig3":
                recs = fig3_operator.records(quick=quick)
                rows = fig3_operator.rows_from(recs)
                summary["fig3_records"] = recs
            elif name == "batched":
                recs = batched_solve.records(quick=quick)
                rows = batched_solve.rows_from(recs)
                summary["batched_records"] = recs
            elif name == "exchange":
                recs = exchange_select.records(quick=quick)
                rows = exchange_select.main(quick=quick)
                rows += exchange_select.rows_from(recs)
                summary["exchange_records"] = recs
            else:
                rows = list(fn(quick=quick))
            for row in rows:
                print(row, flush=True)
            summary["sections"][name] = rows
        except Exception as e:  # report and continue
            failures += 1
            msg = f"{name},ERROR,{type(e).__name__}: {e}"
            summary["failures"].append(msg)
            print(msg, flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"# wrote {args.json}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
