"""Fault injectors for the solver guardrails.

Each injector models a concrete failure mode of a production solve and is
paired with the detector that must catch it (`core.cg.SolveStatus`):

| injector              | models                              | detector        |
|-----------------------|-------------------------------------|-----------------|
| `nan_at_iteration`    | transient SDC / overflow in A·p     | BREAKDOWN_NAN   |
| `negate_precond`      | sign-corrupted M⁻¹ (r·z < 0)        | BREAKDOWN_INDEF |
| `skew_operator`       | non-symmetric operator corruption   | DIVERGED        |
| `mask_precond`        | partially-zeroed M⁻¹ payload        | STAGNATED       |
| `corrupt_wire`        | corrupted halo/shell wire payload   | any of the above|

Operator/preconditioner wrappers are plain callables — compose them with
`core.resilience.solve_with_fallback`'s ``instrument`` seam (see
`on_attempt`) to fault only specific retry attempts.  `corrupt_wire` is a
context manager because its seam is module state read at trace time:
install it *before* the solve is compiled.

Nothing here is imported by solver code; this module is the testing
surface of the robustness subsystem.
"""
from __future__ import annotations

import contextlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import io_callback

__all__ = [
    "corrupt_wire",
    "mask_precond",
    "nan_at_iteration",
    "negate_precond",
    "on_attempt",
    "skew_operator",
]


def nan_at_iteration(
    operator: Callable[[jax.Array], jax.Array],
    k: int,
    *,
    value: float = float("nan"),
) -> Callable[[jax.Array], jax.Array]:
    """Poison the operator's output on its k-th call (one-shot).

    Call 0 is the initial-residual apply A·x₀; call k ≥ 1 is PCG iteration
    k's A·p.  The fault is *transient*: the host-side call counter keeps
    advancing across solves, so a fallback retry of the same wrapped
    operator runs clean — the model is a single silent-data-corruption
    event, not a broken operator.  Uses an ordered ``io_callback``, so the
    wrapped operator only works on a single device (tests; not inside
    shard_map — corrupt the wire with `corrupt_wire` there).
    """
    counter = {"n": 0}

    def bump() -> np.int32:
        i = counter["n"]
        counter["n"] += 1
        return np.int32(i)

    def wrapped(x: jax.Array) -> jax.Array:
        y = operator(x)
        idx = io_callback(
            bump, jax.ShapeDtypeStruct((), jnp.int32), ordered=True
        )
        return jnp.where(idx == k, jnp.asarray(value, y.dtype), y)

    return wrapped


def skew_operator(
    operator: Callable[[jax.Array], jax.Array], magnitude: float
) -> Callable[[jax.Array], jax.Array]:
    """Add a skew-symmetric corruption s·(roll(x,1) − roll(x,−1)).

    Silently breaks the operator's symmetry while leaving p·Ap untouched
    (the skew part of a quadratic form vanishes), so CG's recurrence blows
    up *without* tripping the indefinite detector — the canonical DIVERGED
    trigger.
    """

    def wrapped(x: jax.Array) -> jax.Array:
        s = jnp.asarray(magnitude, x.dtype)
        return operator(x) + s * (jnp.roll(x, 1) - jnp.roll(x, -1))

    return wrapped


def negate_precond(
    precond: Callable[[jax.Array], jax.Array],
) -> Callable[[jax.Array], jax.Array]:
    """Flip the sign of M⁻¹.

    −M⁻¹ is negative-definite: r·z < 0 from the very first application,
    the BREAKDOWN_INDEFINITE trigger (note p·Ap stays positive — A is
    untouched — which is why the indefinite detector checks r·z too).
    """
    return lambda r: -precond(r)


def mask_precond(
    precond: Callable[[jax.Array], jax.Array], keep_every: int = 7
) -> Callable[[jax.Array], jax.Array]:
    """Zero every ``keep_every``-th component of M⁻¹'s output.

    A rank-deficient (positive *semi*-definite) M⁻¹ — e.g. a partially
    zeroed payload — confines the Krylov space to a subspace that cannot
    represent the solution: the residual settles at a floor and stops
    improving, the STAGNATED trigger.
    """

    def wrapped(r: jax.Array) -> jax.Array:
        z = precond(r)
        mask = (jnp.arange(z.shape[0]) % keep_every != 0).astype(z.dtype)
        return z * mask

    return wrapped


@contextlib.contextmanager
def corrupt_wire(rank: int, *, mode: str = "nan", axis_name: str | None = None):
    """Corrupt every halo/shell payload *sent* by one rank.

    Installs a `comms.halo.wire_transform` hook, so it applies to all four
    exchange primitives (sum / copy / expand / contract) of anything traced
    inside the ``with`` block — install *before* the dist solve is first
    compiled.  The corruption is targeted with ``lax.axis_index``, so only
    ``rank``'s outgoing slabs are touched; every other rank sends clean
    data, yet all ranks must exit the solve on the same iteration with the
    same status (the detector inputs are psum-derived).

    Modes: ``"nan"`` (poison), ``"zero"`` (dropped payload),
    ``"negate"`` (sign corruption), ``"scramble"`` (mis-ordered payload —
    slab rolled by one along its last axis).
    """
    if mode not in ("nan", "zero", "negate", "scramble"):
        raise ValueError(f"unknown corrupt_wire mode {mode!r}")
    from ..comms import halo

    def hook(slab: jax.Array, ax: str) -> jax.Array:
        if axis_name is not None and ax != axis_name:
            return slab
        mine = lax.axis_index(ax) == rank
        if mode == "nan":
            bad = jnp.full_like(slab, jnp.nan)
        elif mode == "zero":
            bad = jnp.zeros_like(slab)
        elif mode == "negate":
            bad = -slab
        else:  # scramble
            bad = jnp.roll(slab, 1, axis=-1)
        return jnp.where(mine, bad, slab)

    with halo.wire_transform(hook):
        yield


def on_attempt(
    attempt: int,
    *,
    operator: Callable | None = None,
    precond: Callable | None = None,
) -> Callable:
    """Build a `solve_with_fallback` ``instrument`` faulting one attempt.

    ``operator`` / ``precond`` are wrappers (e.g. `negate_precond`,
    `skew_operator` partially applied) applied only on the given attempt
    index; every other attempt runs the chain's genuine configuration —
    the shape of a fault that the escalation is supposed to out-run.
    """

    def instrument(i: int, op: Callable, pc: Callable | None):
        if i != attempt:
            return op, pc
        if operator is not None:
            op = operator(op)
        if precond is not None and pc is not None:
            pc = precond(pc)
        return op, pc

    return instrument
