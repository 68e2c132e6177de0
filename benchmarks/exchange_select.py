"""Exchange-routing selection (paper §MPI Communication behavior).

Two layers, matching how hipBone inherits gslib's setup-time selection:

* the legacy *library* sweep (``main``): times all-to-all / pairwise /
  crystal-router over a message-size ladder on 8 emulated ranks —
  reproducing the paper's claim structure that the crystal router wins
  small (latency-bound) messages and pairwise wins large ones;
* the *solver-site* plan build (``records``): runs the actual
  ``comms.plan`` autotuner over every halo-exchange site of a sharded
  pMG solve setup (CG sum, Schwarz expand/contract shells, each coarse
  level's exchanges) and reports per-site candidate timings, the winning
  routing and the analytic wire bytes — the ``exchange_records`` section
  of the benchmark json.
"""
from __future__ import annotations

import json

from benchmarks.spawn import run_child

_CHILD = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.comms.exchange import EXCHANGES

from repro.compat import make_mesh, shard_map
mesh = make_mesh((8,), ("r",))
out = {}
for chunk in [16, 256, 4096, 65536]:
    x = jnp.zeros((64, chunk), jnp.float32)
    row = {}
    for name, fn in EXCHANGES.items():
        f = jax.jit(shard_map(partial(fn, axis_name="r"), mesh=mesh,
                                  in_specs=P("r"), out_specs=P("r")))
        f(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(10):
            f(x).block_until_ready()
        row[name] = (time.perf_counter() - t0) / 10
    row["winner"] = min(row, key=row.get)
    out[chunk] = row
print(json.dumps(out))
"""

# halo-site plan build: the comms.plan autotuner over a real solver setup's
# site list.  Persistence is disabled — this run is timing *evidence*, not
# cache state, and must re-measure every time.
_CHILD_PLAN = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["HIPBONE_EXCHANGE_CACHE"] = ""
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.compat import make_mesh
from repro.comms.topology import ProcessGrid
from repro.comms import plan as xplan
from repro.core.precond import SCHWARZ_INNER_DEGREE
from repro.core.distributed import (
    build_dist_problem, build_pmg_levels, _exchange_sites, _schwarz_setup,
)

cfg = json.loads(os.environ["EXCHANGE_PLAN_CFG"])
grid = ProcessGrid((2, 2, 2))
mesh = make_mesh((8,), ("ranks",))
prob = build_dist_problem(
    cfg["n"], grid, tuple(cfg["local"]), lam=1.0, dtype=jnp.float64
)
levels, _ = build_pmg_levels(prob, None)
schwarz = [
    _schwarz_setup(lvl, min(1, lvl.n_degree - 1), SCHWARZ_INNER_DEGREE)
    for lvl in levels[:-1]
]
sites = _exchange_sites(prob, levels, schwarz)
plan = xplan.build_exchange_plan(
    mesh, grid, prob.axis_name, sites,
    policy="auto", repeats=cfg["repeats"],
)
recs = plan.records()
for r in recs:
    r["n"] = cfg["n"]
print(json.dumps(recs))
"""


def records(quick: bool = True) -> list[dict]:
    """Per-site exchange plan records for the json summary.

    Each record: ``site`` (kind@level), per-candidate ``timings``
    ("routing/wire" -> best seconds), the winning ``routing`` +
    ``wire_dtype``, analytic ``bytes`` on the wire, and the plan
    ``signature`` the persistence layer would key on.
    """
    cfg = {
        "n": 4 if quick else 7,
        "local": [2, 2, 1] if quick else [2, 2, 2],
        "repeats": 3 if quick else 5,
    }
    return run_child(
        _CHILD_PLAN,
        {"EXCHANGE_PLAN_CFG": json.dumps(cfg)},
        section="exchange",
    )


def rows_from(recs: list[dict]) -> list[str]:
    """CSV rows from plan records (one per site, winner + best timings)."""
    rows = ["exchange_plan,site,N,winner,wire,bytes,best_us,candidates"]
    for r in recs:
        best = min(r["timings"].values()) if r["timings"] else float("nan")
        cands = "|".join(
            f"{k}:{v*1e6:.0f}" for k, v in sorted(r["timings"].items())
        )
        rows.append(
            f"exchange_plan,{r['site']},{r.get('n', '')},{r['routing']},"
            f"{r['wire_dtype'] or 'native'},{r['bytes']},{best*1e6:.0f},"
            f"{cands}"
        )
    return rows


def main(quick: bool = True) -> list[str]:
    data = run_child(_CHILD, timeout=600, section="exchange")
    rows = ["exchange,chunk_floats,all_to_all_us,pairwise_us,crystal_us,winner"]
    for chunk, row in data.items():
        rows.append(
            f"exchange,{chunk},{row['all_to_all']*1e6:.0f},"
            f"{row['pairwise']*1e6:.0f},{row['crystal_router']*1e6:.0f},"
            f"{row['winner']}"
        )
    return rows


if __name__ == "__main__":
    print("\n".join(main()))
    print("\n".join(rows_from(records())))
