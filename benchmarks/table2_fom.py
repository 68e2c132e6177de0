"""Table 2 analogue: peak FOM and per-rank FOM vs rank count; weak-scaling
efficiency — plus the NekBone-baseline comparison the paper motivates with.

Runs BOTH storage modes (hipBone assembled vs NekBone scattered) at N=7 on
1..8 emulated ranks and reports the per-iteration data-motion advantage
(the paper's Eq. data-motion analysis realized as measured wall-time).
"""
from __future__ import annotations

from benchmarks.spawn import run_child

_CHILD = r"""
import os, json, time
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={RANKS}"
import jax, numpy as np, jax.numpy as jnp
from repro.compat import make_mesh
from repro.comms.topology import ProcessGrid, factor3
from repro.core.distributed import build_dist_problem, dist_cg, dist_cg_scattered
from repro.core.fom import nekbone_flops_per_iter, cg_iter_bytes, nekbone_iter_bytes

ranks, n, local, n_iter = RANKS, 7, (2, 2, 2), 50
grid = ProcessGrid(factor3(ranks))
mesh = make_mesh((ranks,), ("ranks",))
prob = build_dist_problem(n, grid, local, lam=1.0, dtype=jnp.float32)
rng = np.random.default_rng(0)
b = jnp.asarray(rng.standard_normal((ranks, prob.m3)), jnp.float32)
bL = jnp.take(b, jnp.asarray(prob.l2g.reshape(-1)), axis=1).reshape(
    ranks, prob.e_local, -1)

def bench(run):
    fn = jax.jit(run)
    fn()[1].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        fn()[1].block_until_ready()
    return (time.perf_counter() - t0) / 3

t_asm = bench(dist_cg(prob, mesh, b, n_iter=n_iter))
t_sca = bench(dist_cg_scattered(prob, mesh, bL, n_iter=n_iter))
e_tot = ranks * prob.e_local
flops = nekbone_flops_per_iter(e_tot, n) * n_iter
print(json.dumps({
    "ranks": ranks,
    "fom_assembled": flops / t_asm / 1e9,
    "fom_scattered": flops / t_sca / 1e9,
    "speedup": t_sca / t_asm,
    "bytes_model_ratio": nekbone_iter_bytes(e_tot, n, word=4)
                        / cg_iter_bytes(e_tot, n, word=4),
}))
"""


def _run(ranks: int) -> dict:
    child = _CHILD.replace("RANKS", str(ranks))
    return run_child(child, section="table2")


def main(quick: bool = True) -> list[str]:
    rows = [
        "table2,ranks,fom_assembled_gflops,fom_per_rank,weak_scaling_eff_pct,"
        "fom_scattered_gflops,assembled_speedup,bytes_model_ratio"
    ]
    base = None
    for ranks in ([1, 2, 4, 8] if not quick else [1, 4]):
        r = _run(ranks)
        per = r["fom_assembled"] / ranks
        if base is None:
            base = per
        rows.append(
            f"table2,{ranks},{r['fom_assembled']:.2f},{per:.2f},"
            f"{100*per/base:.1f},{r['fom_scattered']:.2f},"
            f"{r['speedup']:.3f},{r['bytes_model_ratio']:.3f}"
        )
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    print("\n".join(main(quick=args.quick)))
