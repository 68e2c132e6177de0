"""Observability: the program's scope names, host spans and compile counter.

Device scopes.  ``scope(name)`` is a ``jax.named_scope`` that puts
``PREFIX + name`` on the JAX name stack of every op traced inside it.  XLA
keeps the name stack as op metadata (``op_name`` in the HLO, ``tf_op`` in a
profiler trace), so a chip trace can be attributed to the solver's layers
(``benchmarks/tpu/scopes.py``).  A scope adds metadata only: the optimized
HLO is the same with and without it.

Host spans.  ``span(name)`` times a block of host code: it records
``(name, start_ns, end_ns, parent)`` in a bounded in-memory list, read back
by ``spans()``, and opens a ``jax.profiler.TraceAnnotation`` of the same
name, so that in a traced run the span sits on the device trace's clock.

Compile counter.  Backend compiles (``jax.monitoring``'s backend-compile
event, which a persistent-cache hit also fires) and persistent-cache hits
are counted under the innermost open span (``counters()``), and each open
span counts those that happen while it is open.

Every name is declared in ``SCOPES`` or ``SPANS``; a name with ``{i}`` is a
family, one name per level.  The tables are the contract that trace
reductions and metrics read.
"""
from __future__ import annotations

import collections
import functools
import re
import threading
import time
from typing import NamedTuple

import jax

__all__ = ["PREFIX", "SCOPES", "SPANS", "Span", "counters", "reset", "scope", "span",
           "spanned", "spans"]

PREFIX = "hb."  # no name-stack entry of JAX's own starts with it

# device scopes (jax.named_scope) -> where each sits
SCOPES = {
    "cg.init": "core/cg.py _pcg: r0 = b - A x0, r0.r0 and the first M^-1",
    "cg.iteration": "core/cg.py _pcg: one (P)CG step and the loop test; "
                    "its self time is the vector stages (dots, axpys, beta, detectors)",
    "cg.operator": "core/cg.py _pcg: every A-apply of the Krylov recurrence",
    "cg.precond": "core/cg.py _pcg: every z = M^-1 r",
    "cg.allreduce": "core/distributed.py: every psum of the recurrence scalars",
    "op.scatter": "core/operator.py poisson_assembled: x_L = Z x_G (take)",
    "op.local": "core/operator.py poisson_assembled: the element operator, XLA or Pallas",
    "op.gather": "core/operator.py poisson_assembled: Z^T y_L (segment sum)",
    "op.fused": "kernels/ops.py: the fused assembled operator, one Pallas pass",
    "op.scattered": "core/operator.py poisson_scattered: (Z Z^T S_L + lambda) x_L",
    "pmg.l{i}": "core/precond.py V-cycle: level i's own work (smoothing, residual, "
                "restriction, prolongation and correction), not the levels below",
    "pmg.coarse": "core/precond.py V-cycle: the coarsest level's solve",
    "halo.sum": "comms/halo.py sum_exchange",
    "halo.copy": "comms/halo.py copy_exchange",
    "halo.expand": "comms/halo.py expand_exchange",
    "halo.contract": "comms/halo.py contract_exchange",
    "halo.site.rhs": "core/distributed.py: the right-hand side made consistent",
    "halo.site.operator": "core/distributed.py: the A-apply's exchanges",
    "halo.site.galerkin": "core/distributed.py: a materialized Galerkin level's exchanges",
    "halo.site.diag": "core/distributed.py: the assembled diagonal's exchange",
    "halo.site.prolong": "core/distributed.py: the prolongation's exchange",
    "halo.site.restrict": "core/distributed.py: the restriction's exchange",
    "halo.site.schwarz": "core/distributed.py: the Schwarz smoother's exchanges",
    "halo.site.scattered": "core/distributed.py: the scattered operator's exchange",
}

# host spans (span) -> what each times
SPANS = {
    "setup.build_problem": "core/operator.py build_problem: the whole host build",
    "setup.mesh": "the box mesh and the coefficient fields, on the host",
    "setup.geometry": "geometric factors, derivative matrix, inverse degree, masks",
    "setup.upload": "the problem's arrays handed to the device",
    "setup.precond": "core/precond.py make_preconditioner: the whole set-up",
    "setup.precond.l{i}": "one pMG level: coarsening and transfers to it, "
                          "diagonal, Lanczos interval; the coarsest its coarse solve",
    "engine.dispatch": "serving/engine.py: one batched dispatch",
    "engine.setup_lookup": "the setup-cache lookup or build",
    "engine.rhs_stack": "the right-hand sides stacked into one block",
    "engine.solve": "the batched solve call (trace, compile, enqueue)",
    "engine.block": "waiting for the solve's result",
}


def _pattern(table: dict) -> re.Pattern:
    alts = (re.escape(n).replace(re.escape("{i}"), r"\d+") for n in table)
    return re.compile("|".join(alts))


_SCOPE_RE, _SPAN_RE = _pattern(SCOPES), _pattern(SPANS)


def _check(name: str, pattern: re.Pattern, table: str) -> str:
    if not pattern.fullmatch(name):
        raise ValueError(f"{name!r} is not declared in repro.obs.{table}")
    return PREFIX + name


def scope(name: str):
    """``jax.named_scope`` of a declared scope; a context manager or decorator."""
    return jax.named_scope(_check(name, _SCOPE_RE, "SCOPES"))


# ------------------------------------------------------------ host spans
class Span(NamedTuple):
    name: str
    start_ns: int     # time.perf_counter_ns()
    end_ns: int
    parent: str | None
    compiles: int     # backend compiles while the span was open
    compile_s: float


MAX_SPANS = 4096
_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_counts: dict = {}
_local = threading.local()


def _open() -> list:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


class span:
    """Time a block of host code under a declared span name.

    ``with span("setup.precond") as s: ...``; afterwards ``s.compiles``
    and ``s.compile_s`` hold the backend compiles made inside it.
    """

    __slots__ = ("name", "compiles", "compile_s", "_parent", "_start", "_note")

    def __init__(self, name: str):
        self._note = jax.profiler.TraceAnnotation(_check(name, _SPAN_RE, "SPANS"))
        self.name = name

    def __enter__(self):
        stack = _open()
        self._parent = stack[-1].name if stack else None
        self.compiles, self.compile_s = 0, 0.0
        stack.append(self)
        self._note.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._note.__exit__(*exc)
        _open().pop()
        _spans.append(Span(self.name, self._start, end, self._parent,
                           self.compiles, self.compile_s))
        return False


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    _check(name, _SPAN_RE, "SPANS")

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def spans(name: str | None = None) -> list[Span]:
    """Recorded spans in the order they ended; only ``name``'s if given."""
    return [s for s in _spans if name is None or s.name == name]


def counters() -> dict:
    """``{innermost open span or None: {"compiles", "compile_s", "cache_hits"}}``."""
    return {k: dict(v) for k, v in _counts.items()}


def reset():
    """Forget every recorded span and count."""
    _spans.clear()
    _counts.clear()


# ------------------------------------------------------------ compile counter
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _tally(key: str, amount) -> None:
    stack = _open()
    inner = stack[-1].name if stack else None
    slot = _counts.setdefault(inner, {"compiles": 0, "compile_s": 0.0, "cache_hits": 0})
    slot[key] += amount
    if key != "cache_hits":
        for s in stack:
            setattr(s, key, getattr(s, key) + amount)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _tally("compiles", 1)
        _tally("compile_s", duration)


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        _tally("cache_hits", 1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
