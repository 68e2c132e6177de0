"""Solver serving engine: slot-batched multi-RHS solves over a setup cache.

The production shape of hipBone's workload is a *service*: a stream of
screened-Poisson solve requests against a small population of problem
setups (same mesh every time step, a handful of λ/preconditioner
configurations).  The engine turns that stream into efficient batched
dispatches:

  1. **Group** pending requests by their dispatch key — the
     :func:`core.solver_cache.solver_setup_key` (mesh signature, N, λ,
     precond config, dtype) plus the solve-time knobs (tol, n_iter,
     cg_variant).  Requests in one group share everything but the RHS.
  2. **Slot-batch** each group into slabs of ``max_batch`` columns and
     stack the RHS vectors into a (B, n_global) block.
  3. **Dispatch** one :func:`core.cg.batched_cg_assembled` per slab —
     one operator apply streams all B columns; columns stop
     independently, so an easy RHS doesn't pay for its hard neighbour's
     iterations.

Setup is cached across dispatches (:class:`core.solver_cache.SolverCache`):
the first slab of a key pays the build (operator + preconditioner chain),
every later slab — and every later *request batch* — reuses it untouched.
Each dispatch appends a json-ready record with the cache hit/miss state,
wall times, the backend compiles made inside the dispatch (``repro.obs``)
and per-column iterations/status, so the batched-solve benchmark (and a
service log) can assert the hit path did zero setup.

The seed's LLM decode engine this replaced lives on in
``repro.serving.lm`` (same slot-batching idea, token streams instead of
RHS columns); ``examples/serve_lm.py`` still drives it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from .. import obs
from ..core.cg import batched_cg_assembled, status_name
from ..core.solver_cache import SolverCache, SolverSetup, solver_setup_key
from ..kernels import ops

__all__ = ["SolveRequest", "SolveResponse", "SolverEngine", "SolverServeConfig"]


@dataclasses.dataclass(frozen=True)
class SolverServeConfig:
    """Engine knobs (not part of any cache key).

    ``max_batch`` bounds one dispatch's slot count; ``fuse`` forces the
    Pallas fused vector stages on/off (None = the per-dtype auto policy
    ``kernels.ops.should_fuse_streams``); ``interpret`` is the usual
    Pallas CPU/TPU switch for those stages; ``max_cache_entries`` bounds
    the setup cache LRU-style.
    """

    max_batch: int = 16
    fuse: bool | None = None
    interpret: bool | None = None
    max_cache_entries: int | None = None

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    """One screened-Poisson solve: A(prob) x = b to tol, preconditioned.

    ``precond`` holds the ``make_preconditioner`` keyword config (degree,
    pmg ladder, …); ``tol``/``n_iter``/``cg_variant`` are solve-time knobs
    — they group dispatches but never touch the setup cache key.
    """

    prob: Any  # core.operator.PoissonProblem
    b: jax.Array
    kind: str = "none"
    precond: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    tol: float | None = 1e-8
    n_iter: int = 200
    cg_variant: str = "standard"


@dataclasses.dataclass(frozen=True)
class SolveResponse:
    """One column's result plus the dispatch context it rode in."""

    x: jax.Array
    rdotr: float
    iterations: int
    status: int
    status_name: str
    setup_cache: str  # "hit" | "miss" — the setup-cache state this dispatch saw
    batch_size: int   # columns in the slab this request was solved with
    solve_s: float    # wall time of the whole slab's batched solve

    @property
    def converged(self) -> bool:
        return self.status == 0


class SolverEngine:
    """Accepts solve requests, groups them by setup, dispatches batched.

    ``submit`` queues; ``flush`` solves everything pending and returns
    responses in submission order; ``solve`` is submit-all-then-flush.
    A shared :class:`SolverCache` may be injected (e.g. pre-warmed by a
    benchmark); otherwise the engine owns one.
    """

    def __init__(
        self,
        cfg: SolverServeConfig | None = None,
        cache: SolverCache | None = None,
    ):
        self.cfg = cfg or SolverServeConfig()
        self.cache = cache or SolverCache(
            max_entries=self.cfg.max_cache_entries
        )
        self._pending: list[SolveRequest] = []
        self.records: list[dict] = []

    # -- request intake ------------------------------------------------

    def submit(self, req: SolveRequest) -> int:
        """Queue a request; returns its ticket (position in flush order)."""
        if req.b.ndim != 1:
            raise ValueError(
                f"SolveRequest.b must be a single (n_global,) RHS column, "
                f"got shape {req.b.shape}; submit one request per column"
            )
        if req.b.shape[0] != req.prob.n_global:
            raise ValueError(
                f"RHS length {req.b.shape[0]} != n_global {req.prob.n_global}"
            )
        self._pending.append(req)
        return len(self._pending) - 1

    def solve(self, requests: list[SolveRequest]) -> list[SolveResponse]:
        for req in requests:
            self.submit(req)
        return self.flush()

    def solve_one(self, req: SolveRequest) -> SolveResponse:
        self.submit(req)
        return self.flush()[0]

    # -- dispatch ------------------------------------------------------

    def _dispatch_key(self, req: SolveRequest) -> tuple:
        return solver_setup_key(req.prob, req.kind, **dict(req.precond)) + (
            ("tol", req.tol),
            ("n_iter", int(req.n_iter)),
            ("cg_variant", req.cg_variant),
        )

    def _cg_kwargs(self, req: SolveRequest, setup: SolverSetup) -> dict:
        fuse = (
            ops.should_fuse_streams(req.prob.dtype)
            if self.cfg.fuse is None
            else self.cfg.fuse
        )
        kwargs: dict = {
            "n_iter": int(req.n_iter),
            "tol": req.tol,
            "precond": setup.precond,
            "cg_variant": req.cg_variant,
        }
        if fuse:
            # per-column Pallas stage; batched_cg_assembled's vmap batches
            # it into the 2-D (B, rows, 128) layout (kernels/streams.py)
            interp = self.cfg.interpret
            kwargs["fused_update"] = lambda r, ap, alpha: ops.fused_axpy_dot(
                r, ap, alpha, interpret=interp
            )
        return kwargs

    def flush(self) -> list[SolveResponse]:
        """Solve all pending requests; responses in submission order."""
        pending, self._pending = self._pending, []
        groups: dict[tuple, list[int]] = {}
        for ticket, req in enumerate(pending):
            groups.setdefault(self._dispatch_key(req), []).append(ticket)

        responses: list[SolveResponse | None] = [None] * len(pending)
        for key, tickets in groups.items():
            for lo in range(0, len(tickets), self.cfg.max_batch):
                slab = tickets[lo : lo + self.cfg.max_batch]
                self._dispatch(key, [pending[t] for t in slab], slab, responses)
        return responses  # type: ignore[return-value]

    def _dispatch(
        self,
        key: tuple,
        reqs: list[SolveRequest],
        tickets: list[int],
        responses: list,
    ) -> None:
        req0 = reqs[0]
        with obs.span("engine.dispatch") as dispatch:
            with obs.span("engine.setup_lookup"):
                setup_key = solver_setup_key(
                    req0.prob, req0.kind, **dict(req0.precond)
                )
                state = "hit" if setup_key in self.cache else "miss"
                setup = self.cache.get_or_build(
                    req0.prob, req0.kind, **dict(req0.precond)
                )
            with obs.span("engine.rhs_stack"):
                b_block = jnp.stack([r.b for r in reqs])
            t0 = time.perf_counter()
            with obs.span("engine.solve"):
                res = batched_cg_assembled(
                    setup.operator, b_block, **self._cg_kwargs(req0, setup)
                )
            with obs.span("engine.block"):
                jax.block_until_ready(res.x)
            solve_s = time.perf_counter() - t0

        iters = [int(i) for i in res.iterations]
        stats = [int(s) for s in res.status]
        for col, ticket in enumerate(tickets):
            responses[ticket] = SolveResponse(
                x=res.x[col],
                rdotr=float(res.rdotr[col]),
                iterations=iters[col],
                status=stats[col],
                status_name=status_name(stats[col]),
                setup_cache=state,
                batch_size=len(reqs),
                solve_s=solve_s,
            )
        self.records.append(
            {
                "kind": req0.kind,
                "batch": len(reqs),
                "setup_cache": state,
                "setup_build_s": setup.build_s if state == "miss" else 0.0,
                "solve_s": solve_s,
                "per_solve_s": solve_s / len(reqs),
                # backend compiles inside this dispatch (solve_s includes them)
                "compiles": dispatch.compiles,
                "compile_s": dispatch.compile_s,
                "iterations": iters,
                "status": stats,
                "cache": self.cache.stats(),
            }
        )
