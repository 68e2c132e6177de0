"""Topology-aware autotuned exchange plans (paper §MPI Communication).

hipBone inherits gslib's setup-time exchange selection: for every gather
-scatter it *times* the candidate routings (pairwise, all-to-all,
crystal router) on the actual machine and caches the winner per cluster.
This module is that idea for the structured halo exchanges of the
distributed solver: every exchange *site* — the CG ``sum_exchange``, the
Schwarz ``expand``/``contract`` shells, each pMG level's exchanges (where
payloads shrink ~8× per rung and the latency/bandwidth tradeoff flips) —
is timed over the actual (process grid, box shape, dtype, wire dtype)
at solver setup, and the winning routing is recorded in an
:class:`ExchangePlan`.

Every candidate routing reproduces the face sweep's IEEE reduction tree
bit-for-bit at the native wire (see ``comms.halo``), so the plan is a
pure performance knob: PCG iteration counts are identical whatever the
plan says.  A narrowed wire dtype is an opt-in tuning axis
(``wire="auto"``) that does change rounding points — it never activates
unless asked for.

Plans persist to disk keyed by a content signature (grid shape, site
set, dtype, wire candidates, device topology — hashed with
``core.solver_cache.content_signature``, the same keying style as the
solver cache's mesh signatures), so a cluster is tuned once: the second
setup with the same signature loads the plan without re-timing.

Environment knobs:

  * ``HIPBONE_EXCHANGE`` — force a policy (``face_sweep``, ``crystal``,
    ``fused``) or ``auto`` for every solve that doesn't pass an explicit
    ``exchange=``;
  * ``HIPBONE_EXCHANGE_CACHE`` — plan cache directory (default
    ``~/.cache/hipbone/exchange_plans``; set to an empty string to
    disable persistence).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import obs
from ..compat import shard_map
from ..core.solver_cache import content_signature
from . import halo
from .topology import ProcessGrid

__all__ = [
    "ExchangePlan",
    "ExchangeSite",
    "SitePlan",
    "POLICIES",
    "build_exchange_plan",
    "clear_plan_cache",
    "default_policy",
    "plan_cache_dir",
    "resolve_routing",
    "tally_routes",
]

POLICIES = ("auto", "face_sweep", "crystal", "fused")

_ROUTING_MENUS = {
    "sum": halo.SUM_ROUTINGS,
    "copy": halo.PAIR_ROUTINGS,
    "expand": halo.PAIR_ROUTINGS,
    "contract": halo.PAIR_ROUTINGS,
}

# in-process plan memo (signature -> ExchangePlan): repeated setups in one
# process skip even the disk read
_MEMORY: dict[str, "ExchangePlan"] = {}


def default_policy() -> str:
    """The session's exchange policy: ``HIPBONE_EXCHANGE`` or face_sweep."""
    return os.environ.get("HIPBONE_EXCHANGE", "face_sweep")


def plan_cache_dir() -> str | None:
    """Plan persistence directory (None = persistence disabled)."""
    d = os.environ.get(
        "HIPBONE_EXCHANGE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "hipbone", "exchange_plans"),
    )
    return d or None


def resolve_routing(kind: str, name: str) -> str:
    """Map a policy name onto ``kind``'s routing menu.

    The copy/expand/contract shells have no staged variant distinct from
    the per-dim sweep, so a forced ``crystal`` policy falls back cleanly
    to ``face_sweep`` for them (the sum sites still get the staged route).
    """
    menu = _ROUTING_MENUS[kind]
    if name in menu:
        return name
    if name == "crystal":
        return "face_sweep"
    raise ValueError(f"unknown exchange routing {name!r} for {kind!r} sites")


@dataclasses.dataclass(frozen=True)
class ExchangeSite:
    """One exchange call site of a distributed solve, as seen by the tuner.

    ``kind`` is the primitive (``sum``/``copy``/``expand``/``contract``),
    ``level`` the pMG level (0 = fine), ``box_shape`` the [z, y, x] shape
    of the box the primitive is applied to (for ``contract`` that is the
    *expanded* box), ``depth`` the shell depth of expand/contract sites.
    """

    kind: str
    level: int
    box_shape: tuple[int, int, int]
    dtype: str
    depth: int = 0

    @property
    def key(self) -> str:
        return f"{self.kind}@{self.level}"

    def descriptor(self) -> tuple:
        """Identity WITHOUT the level: same-shaped sites share one timing."""
        return (self.kind, tuple(self.box_shape), self.dtype, self.depth)


@dataclasses.dataclass(frozen=True)
class SitePlan:
    """The tuner's verdict for one site: winner + the measured evidence."""

    site: str
    routing: str
    wire_dtype: str | None
    bytes: int
    timings: Mapping[str, float]  # "{routing}/{wire}" -> best seconds

    def to_json(self) -> dict:
        return {
            "site": self.site,
            "routing": self.routing,
            "wire_dtype": self.wire_dtype,
            "bytes": self.bytes,
            "timings": dict(self.timings),
        }


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Per-site routing decisions for one (grid, site set, device) identity.

    ``lookup(kind, level)`` is the hot-path accessor: returns the
    ``(routing, wire_dtype)`` pair an exchange call should use.  Sites the
    plan never saw resolve through the plan's policy (a forced plan has no
    timings at all and resolves everything this way).
    """

    policy: str
    signature: str
    sites: Mapping[str, SitePlan]
    from_cache: bool = False
    timed: bool = False

    def lookup(self, kind: str, level: int = 0) -> tuple[str, Any | None]:
        sp = self.sites.get(f"{kind}@{level}")
        if sp is None:
            name = self.policy if self.policy != "auto" else "face_sweep"
            return resolve_routing(kind, name), None
        wire = None if sp.wire_dtype is None else jnp.dtype(sp.wire_dtype)
        return sp.routing, wire

    def records(self) -> list[dict]:
        """Json-ready per-site rows (the BENCH ``exchange_records`` shape)."""
        return [
            {**self.sites[k].to_json(), "policy": self.policy,
             "signature": self.signature, "from_cache": self.from_cache}
            for k in sorted(self.sites)
        ]


def tally_routes(plan: ExchangePlan, sites: list[ExchangeSite]) -> None:
    """Count each site under the routing ``plan`` gives it (``xch.route.*``)."""
    for site in sites:
        obs.tally("xch.route." + plan.lookup(site.kind, site.level)[0])


def _forced_plan(policy: str, signature: str = "") -> ExchangePlan:
    return ExchangePlan(policy=policy, signature=signature, sites={})


def _site_bytes(grid: ProcessGrid, site: ExchangeSite, wire: Any | None) -> int:
    """Analytic wire bytes per exchange application (face-sweep route)."""
    item = jnp.dtype(wire if wire is not None else site.dtype).itemsize
    elems = 1
    for s in site.box_shape:
        elems *= s
    total = 0
    per_round = 2 if site.kind in ("sum", "expand", "contract") else 1
    for d in range(3):
        if grid.shape[d] == 1:
            continue
        face = elems // site.box_shape[2 - d]
        width = max(site.depth, 1)
        total += per_round * face * width * item
    return total


def _site_apply(
    grid: ProcessGrid, axis_name: str, site: ExchangeSite, routing: str,
    wire: Any | None,
):
    if site.kind == "sum":
        return lambda b: halo.sum_exchange(b, grid, axis_name, wire, routing)
    if site.kind == "copy":
        return lambda b: halo.copy_exchange(b, grid, axis_name, wire, routing)
    if site.kind == "expand":
        return lambda b: halo.expand_exchange(
            b, grid, axis_name, site.depth, wire, routing
        )
    if site.kind == "contract":
        return lambda b: halo.contract_exchange(
            b, grid, axis_name, site.depth, wire, routing
        )
    raise ValueError(f"unknown exchange site kind: {site.kind!r}")


def _time_candidate(
    mesh, grid: ProcessGrid, axis_name: str, site: ExchangeSite,
    routing: str, wire: Any | None, repeats: int,
) -> float:
    apply = _site_apply(grid, axis_name, site, routing, wire)
    fn = jax.jit(
        shard_map(
            lambda b: apply(b[0])[None],
            mesh=mesh,
            in_specs=P(axis_name),
            out_specs=P(axis_name),
        )
    )
    x = jnp.ones((grid.size, *site.box_shape), jnp.dtype(site.dtype))
    fn(x).block_until_ready()  # compile outside the timed region
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _wire_candidates(site: ExchangeSite, wire: str) -> list[Any | None]:
    """The wire-dtype axis of the search space for one site.

    ``"native"`` pins the box dtype (the default: candidate routings stay
    bit-identical).  ``"auto"`` adds fp32-on-the-wire for fp64 boxes —
    an opt-in tradeoff that moves rounding points (each routing stays
    replica-consistent, but iteration counts may shift).  A concrete
    dtype name forces that wire.
    """
    if wire == "native":
        return [None]
    if wire == "auto":
        cands: list[Any | None] = [None]
        if jnp.dtype(site.dtype).itemsize > 4:
            cands.append(jnp.float32)
        return cands
    return [jnp.dtype(wire)]


def build_exchange_plan(
    mesh,
    grid: ProcessGrid,
    axis_name: str,
    sites: list[ExchangeSite],
    *,
    policy: str | None = None,
    wire: str = "native",
    repeats: int = 3,
    cache_dir: "str | None" = ...,
) -> ExchangePlan:
    """Build (or load) the exchange plan for one distributed solve setup.

    A non-``auto`` policy forces that routing at every site — no timing,
    no persistence, nothing to load.  ``auto`` resolves in order: the
    in-process memo, the on-disk plan for the same content signature,
    and finally a measured sweep over every (routing, wire) candidate per
    site *class* (sites sharing (kind, box shape, dtype, depth) share one
    timing — coarse pMG levels of equal shape are not re-measured), whose
    winners are persisted for the next process.
    """
    policy = default_policy() if policy is None else policy
    if policy not in POLICIES:
        raise ValueError(
            f"unknown exchange policy {policy!r}; expected one of {POLICIES}"
        )
    if policy != "auto":
        return _forced_plan(policy)
    if cache_dir is ...:
        cache_dir = plan_cache_dir()
    devices = [(d.platform, str(d.device_kind)) for d in mesh.devices.flat]
    signature = content_signature(
        "exchange-plan-v1",
        tuple(grid.shape),
        sorted((s.key, s.descriptor()) for s in sites),
        wire,
        devices,
    )
    cached = _MEMORY.get(signature)
    if cached is not None:
        return cached
    loaded = _load_plan(signature, cache_dir)
    if loaded is not None:
        _MEMORY[signature] = loaded
        return loaded

    timings_by_class: dict[tuple, dict[str, float]] = {}
    site_plans: dict[str, SitePlan] = {}
    for site in sites:
        cls = site.descriptor()
        if cls not in timings_by_class:
            sweep: dict[str, float] = {}
            for routing in _ROUTING_MENUS[site.kind]:
                for wdt in _wire_candidates(site, wire):
                    label = f"{routing}/{'native' if wdt is None else jnp.dtype(wdt).name}"
                    sweep[label] = _time_candidate(
                        mesh, grid, axis_name, site, routing, wdt, repeats
                    )
            timings_by_class[cls] = sweep
        sweep = timings_by_class[cls]
        win = min(sweep, key=sweep.get)
        routing, wire_name = win.split("/")
        site_plans[site.key] = SitePlan(
            site=site.key,
            routing=routing,
            wire_dtype=None if wire_name == "native" else wire_name,
            bytes=_site_bytes(
                grid, site, None if wire_name == "native" else wire_name
            ),
            timings=sweep,
        )
    plan = ExchangePlan(
        policy="auto", signature=signature, sites=site_plans, timed=True
    )
    _MEMORY[signature] = plan
    _save_plan(plan, cache_dir)
    return plan


def _plan_path(signature: str, cache_dir: str) -> str:
    return os.path.join(cache_dir, f"plan_{signature}.json")


def _save_plan(plan: ExchangePlan, cache_dir: str | None) -> None:
    if cache_dir is None:
        return
    os.makedirs(cache_dir, exist_ok=True)
    payload = {
        "signature": plan.signature,
        "policy": plan.policy,
        "sites": [plan.sites[k].to_json() for k in sorted(plan.sites)],
    }
    path = _plan_path(plan.signature, cache_dir)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)  # atomic: concurrent setups never see half a plan


def _load_plan(signature: str, cache_dir: str | None) -> ExchangePlan | None:
    if cache_dir is None:
        return None
    path = _plan_path(signature, cache_dir)
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if payload.get("signature") != signature:
        return None
    sites = {
        s["site"]: SitePlan(
            site=s["site"],
            routing=s["routing"],
            wire_dtype=s.get("wire_dtype"),
            bytes=int(s.get("bytes", 0)),
            timings=dict(s.get("timings", {})),
        )
        for s in payload.get("sites", [])
    }
    return ExchangePlan(
        policy=payload.get("policy", "auto"),
        signature=signature,
        sites=sites,
        from_cache=True,
        timed=False,
    )


def clear_plan_cache(cache_dir: "str | None" = ...) -> None:
    """Drop the in-process memo and (optionally) the on-disk plans.

    Tests use this to force a re-time; pass ``cache_dir=None`` to leave
    the disk alone.
    """
    _MEMORY.clear()
    if cache_dir is ...:
        cache_dir = plan_cache_dir()
    if cache_dir is None or not os.path.isdir(cache_dir):
        return
    for name in os.listdir(cache_dir):
        if name.startswith("plan_") and name.endswith(".json"):
            try:
                os.remove(os.path.join(cache_dir, name))
            except OSError:
                pass


# re-exported for call sites that only need a forced plan (tests, tools)
forced_plan = _forced_plan
