"""Figs. 4-6 reproduction: full-benchmark scaling across ranks.

The paper sweeps problem sizes over 1..64 GPUs and plots FOM (GFLOPS) and
throughput = DOFs*iters/(ranks*time) (Eq. 6). We run the full distributed
hipBone CG on 1/2/4/8 emulated devices (subprocesses with
--xla_force_host_platform_device_count, so this bench itself keeps a
1-device view) across a problem-size sweep, and report both metrics.
Wall-clock here is host-CPU emulation — the shape of the curves (weak-
scaling collapse at large DOFs/rank) is the reproducible signal, not the
absolute GFLOPS; TPU absolutes live in §Roofline.
"""
from __future__ import annotations

from benchmarks.spawn import run_child

_CHILD = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={RANKS}"
import jax, numpy as np, jax.numpy as jnp
from repro.compat import make_mesh
from repro.comms.topology import ProcessGrid, factor3
from repro.core.distributed import build_dist_problem, dist_cg
from repro.core.fom import nekbone_flops_per_iter

ranks = RANKS
n = DEGREE
local = LOCAL
n_iter = 50
grid = ProcessGrid(factor3(ranks))
mesh = make_mesh((ranks,), ("ranks",))
prob = build_dist_problem(n, grid, local, lam=1.0, dtype=jnp.float32)
rng = np.random.default_rng(0)
b = jnp.asarray(rng.standard_normal((ranks, prob.m3)), jnp.float32)
run = jax.jit(dist_cg(prob, mesh, b, n_iter=n_iter))
run()[1].block_until_ready()          # compile + warm
t0 = time.perf_counter()
reps = 3
for _ in range(reps):
    run()[1].block_until_ready()
dt = (time.perf_counter() - t0) / reps
e_tot = ranks * prob.e_local
dofs = prob.n_global
fom = nekbone_flops_per_iter(e_tot, n) * n_iter / dt / 1e9
thru = dofs * n_iter / (ranks * dt)
print(json.dumps({"ranks": ranks, "N": n, "dofs": dofs, "time_s": dt,
                  "fom_gflops": fom, "throughput": thru}))
"""


def _run(ranks: int, degree: int, local: tuple) -> dict:
    code = (
        _CHILD.replace("RANKS", str(ranks))
        .replace("DEGREE", str(degree))
        .replace("LOCAL", str(local))
    )
    return run_child(code, section="fig456")


def main(quick: bool = True) -> list[str]:
    rows = ["fig456,N,ranks,dofs,dofs_per_rank,time_s,fom_gflops,throughput"]
    sizes = {7: [(1, 1, 1), (2, 2, 2)], 15: [(1, 1, 1)]} if quick else {
        7: [(1, 1, 1), (2, 2, 2), (4, 4, 4)],
        15: [(1, 1, 1), (2, 2, 2)],
    }
    rank_list = [1, 2, 4, 8]
    for degree, locals_ in sizes.items():
        for local in locals_:
            for ranks in rank_list:
                try:
                    r = _run(ranks, degree, local)
                except RuntimeError as e:
                    rows.append(f"fig456,{degree},{ranks},ERROR,{e}")
                    continue
                rows.append(
                    f"fig456,{degree},{r['ranks']},{r['dofs']},"
                    f"{r['dofs']//r['ranks']},{r['time_s']:.4f},"
                    f"{r['fom_gflops']:.2f},{r['throughput']:.3e}"
                )
    return rows


if __name__ == "__main__":
    print("\n".join(main(quick=False)))
