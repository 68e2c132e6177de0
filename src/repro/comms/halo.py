"""Structured 3-D halo communication for the distributed Poisson operator.

Each rank owns a padded DOF box of shape (mx, my, mz); interface points are
replicated on every sharing rank. Two primitives, both built from static
``lax.ppermute`` face shifts (2 per partitioned dimension):

  * ``sum_exchange``  — assemble partial sums at interface points AND leave
    every replica holding the summed value (the gather Z^T fused with the
    scatter-side refresh; see DESIGN.md: the padded-consistent storage
    merges hipBone's two communication phases into one).
  * ``copy_exchange`` — refresh replicas from the canonical owner only
    (used by the paper-faithful two-phase mode and by tests).

Sequential dimension sweeps propagate edge/corner contributions without
explicit 26-neighbor messages — the structured-grid trick NekBone's
gslib setup discovers generically.

All functions run inside shard_map over ``axis_name`` whose size equals
``grid.size``. Boxes are passed as 3-D arrays indexed [z, y, x]
(x fastest in the flat layout).

**Wire precision.**  Payload dtype follows the box dtype, so the
mixed-precision preconditioner path (fp32 boxes inside an fp64 PCG) halves
its wire bytes with no code here.  Every primitive additionally accepts
``wire_dtype``: faces/shells are rounded to that dtype just before the
``ppermute`` and widened back on receipt — fp32 wires under fp64 boxes for
payload-bound exchanges where the *accumulation* must stay wide.  Summed
exchanges still accumulate in the box dtype; only the transported slab is
narrowed, and any interface value that travels is rounded on the owning
rank as well, so owner and replicas keep holding the same value (the
consistency contract of the padded box survives the narrow wire).
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from .topology import ProcessGrid

__all__ = [
    "sum_exchange",
    "copy_exchange",
    "expand_exchange",
    "contract_exchange",
    "rank_coords",
    "wire_transform",
    "SUM_ROUTINGS",
    "PAIR_ROUTINGS",
]

# Routing menus per exchange kind.  ``sum_exchange`` has three candidates
# (the per-dim face sweep, the staged bidirectional route, the fused
# one-round route); the copy/expand/contract shells have no staged
# variant distinct from the sweep, so their menu is two-wide and
# ``comms.plan.resolve_routing`` falls "crystal" back to "face_sweep".
# At the box dtype's native wire every routing reproduces the face
# sweep's IEEE reduction tree bit-for-bit — routing is a performance
# knob, never a semantics change (see the per-routing docstrings).
SUM_ROUTINGS = ("face_sweep", "crystal", "fused")
PAIR_ROUTINGS = ("face_sweep", "fused")

# Fault-injection seam: when set, every outgoing payload slab of every
# exchange primitive passes through the hook as ``fn(slab, axis_name)``
# just before its ppermute.  The hook is read at *trace* time, so it must
# be installed before the solve is first compiled (repro.testing.faults
# builds rank-targeted corruptors on top via lax.axis_index).  Production
# code never sets this; the default is a straight pass-through.
_WIRE_HOOK: Callable[[jax.Array, str], jax.Array] | None = None


@contextlib.contextmanager
def wire_transform(fn: Callable[[jax.Array, str], jax.Array]):
    """Temporarily install a wire-payload hook (fault-injection seam)."""
    global _WIRE_HOOK
    prev = _WIRE_HOOK
    _WIRE_HOOK = fn
    try:
        yield
    finally:
        _WIRE_HOOK = prev


def _wire_permute(
    val: jax.Array, axis_name: str, perm, wire_dtype: Any | None
) -> jax.Array:
    """ppermute with an optional cast-on-the-wire of the payload slab."""
    if _WIRE_HOOK is not None:
        val = _WIRE_HOOK(val, axis_name)
    if wire_dtype is None or jnp.dtype(wire_dtype) == val.dtype:
        return lax.ppermute(val, axis_name, perm)
    return lax.ppermute(
        val.astype(wire_dtype), axis_name, perm
    ).astype(val.dtype)


def _wire_round(val: jax.Array, wire_dtype: Any | None) -> jax.Array:
    """Round a slab to the wire dtype in place (idempotent).

    The replica-consistency guard for narrowed wires: any value that
    travels MUST also be rounded on the rank that keeps a copy of it,
    otherwise the owner would hold the exact value while every replica
    holds the rounded one and the same global DOF would differ by rank.
    """
    if wire_dtype is None or jnp.dtype(wire_dtype) == val.dtype:
        return val
    return val.astype(wire_dtype).astype(val.dtype)


def rank_coords(grid: ProcessGrid, axis_name: str) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Traced (i, j, k) coordinates of this rank in the process grid."""
    r = lax.axis_index(axis_name)
    px, py, _ = grid.shape
    return r % px, (r // px) % py, r // (px * py)


# Boxes are stored flat with x fastest (flat = x + mx*(y + my*z)), so the
# reshaped 3-D array is indexed [z, y, x]: spatial dim d lives on array
# axis (2 - d).


def _axis(dim: int) -> int:
    return 2 - dim


def _face(box: jax.Array, dim: int, idx: int) -> jax.Array:
    sl = [slice(None)] * 3
    sl[_axis(dim)] = slice(idx, idx + 1)
    return box[tuple(sl)]


def _set_face(box: jax.Array, dim: int, idx: int, val: jax.Array) -> jax.Array:
    sl = [slice(None)] * 3
    sl[_axis(dim)] = slice(idx, idx + 1)
    return box.at[tuple(sl)].set(val)


def _add_face(box: jax.Array, dim: int, idx: int, val: jax.Array) -> jax.Array:
    sl = [slice(None)] * 3
    sl[_axis(dim)] = slice(idx, idx + 1)
    return box.at[tuple(sl)].add(val)


# Multi-dimension slab slicing for the fused routings: ``spec`` maps a
# spatial dim to an index interval [lo, hi); unspecified dims span fully.


def _slab_sl(spec: dict[int, tuple[int, int]]) -> tuple:
    sl = [slice(None)] * 3
    for dim, (lo, hi) in spec.items():
        sl[_axis(dim)] = slice(lo, hi)
    return tuple(sl)


def _slab(box: jax.Array, spec: dict[int, tuple[int, int]]) -> jax.Array:
    return box[_slab_sl(spec)]


def _pdims(grid: ProcessGrid) -> list[int]:
    return [d for d in range(3) if grid.shape[d] > 1]


def _subsets(dims: list[int]) -> list[tuple[int, ...]]:
    """Nonempty subsets of the partitioned dims, singletons first."""
    out: list[tuple[int, ...]] = []
    for r in range(1, len(dims) + 1):
        out.extend(itertools.combinations(dims, r))
    return out


@obs.scope("halo.sum")
def sum_exchange(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    wire_dtype: Any | None = None,
    routing: str = "face_sweep",
) -> jax.Array:
    """Assemble interface partial sums; all replicas end up consistent.

    ``routing`` selects the message pattern, never the result: at the box
    dtype's native wire all three routings replicate the same IEEE
    reduction tree bit-for-bit (a narrowed ``wire_dtype`` moves the
    rounding points, so routings then agree to rounding error while each
    staying replica-consistent).

      * ``"face_sweep"`` — per partitioned dim: (1) low faces shift down
        and accumulate into the -neighbor's high face (the canonical
        interface slab); (2) the summed high face shifts back up into the
        +neighbor's low face.  6 dependent message rounds, minimal bytes.
      * ``"crystal"`` — staged bidirectional route: per dim ONE round with
        both directions in flight; each side adds own + received
        (commutative IEEE addition makes both sides bitwise equal, so no
        copy-back phase is needed).  3 dependent rounds, same bytes.
      * ``"fused"`` — all dims at once: a gather round shipping every
        face/edge/corner low slab to its diagonal owner, a masked
        broadcast round shipping summed high slabs back.  2 dependent
        rounds, slightly more bytes (edge/corner slabs), up to 7
        concurrent messages per round.

    Boundary ranks receive ppermute zero-fill and are masked.
    ``wire_dtype`` narrows the transported slabs only (sums stay in the
    box dtype); every interface value that travels is rounded on the
    owner too, so all copies of a DOF hold the *same* rounded sum — the
    consistency contract survives the narrow wire.
    """
    if routing == "crystal":
        return _sum_crystal(box, grid, axis_name, wire_dtype)
    if routing == "fused":
        return _sum_fused(box, grid, axis_name, wire_dtype)
    if routing != "face_sweep":
        raise ValueError(f"unknown sum_exchange routing: {routing!r}")
    coords = rank_coords(grid, axis_name)
    for dim in range(3):
        pd = grid.shape[dim]
        if pd == 1:
            continue
        m = box.shape[_axis(dim)]
        c = coords[dim]
        # (1) low face -> -neighbor high face (sum)
        low = _face(box, dim, 0)
        recv = _wire_permute(low, axis_name, grid.shift_perm(dim, -1), wire_dtype)
        box = _add_face(box, dim, m - 1, recv)
        # (2) summed high face -> +neighbor low face (copy); the owner
        # keeps the same rounded value it ships (replica consistency)
        hi = _wire_round(_face(box, dim, m - 1), wire_dtype)
        box = _set_face(box, dim, m - 1, hi)
        recv = _wire_permute(hi, axis_name, grid.shift_perm(dim, +1), wire_dtype)
        keep = _face(box, dim, 0)
        new_low = jnp.where(c > 0, recv, keep)
        box = _set_face(box, dim, 0, new_low)
    return box


def _sum_crystal(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    wire_dtype: Any | None,
) -> jax.Array:
    """Staged bidirectional sum_exchange: one round per partitioned dim.

    Both faces travel concurrently and each side of an interface computes
    own + received itself, so the sweep's copy-back phase disappears: the
    owner adds its high face to the received low face while the +neighbor
    adds the received high face to its own low face.  IEEE addition is
    commutative (bitwise), so both sides hold the identical sum — the
    crystal-router idea (halve the dependent rounds by folding data
    bidirectionally per stage) applied to the structured face exchange,
    and valid on any grid shape, not just powers of two.
    """
    coords = rank_coords(grid, axis_name)
    for dim in range(3):
        pd = grid.shape[dim]
        if pd == 1:
            continue
        m = box.shape[_axis(dim)]
        c = coords[dim]
        keep = _face(box, dim, 0)
        low = _wire_round(keep, wire_dtype)
        hi = _wire_round(_face(box, dim, m - 1), wire_dtype)
        # both directions in one round, on the *original* faces
        recv_hi = _wire_permute(low, axis_name, grid.shift_perm(dim, -1), wire_dtype)
        recv_lo = _wire_permute(hi, axis_name, grid.shift_perm(dim, +1), wire_dtype)
        # owner: own-hi + recv-low == sweep's accumulate; replica:
        # recv-hi + own-low — same operands, commutative, bitwise equal
        new_hi = _wire_round(hi + recv_hi, wire_dtype)
        new_lo = jnp.where(c > 0, _wire_round(recv_lo + low, wire_dtype), keep)
        box = _set_face(box, dim, m - 1, new_hi)
        box = _set_face(box, dim, 0, new_lo)
    return box


def _gather_tree(
    recv: dict[tuple[int, ...], jax.Array], pdims: list[int], D: tuple[int, ...]
) -> jax.Array:
    """Nested slab combination replicating the face sweep's reduction tree.

    The sweep's dim-d stage ships a low face that already contains the
    accumulated results of all earlier stages; shipped directly instead,
    the same nesting is rebuilt locally: the slab for dim set ``D`` folds
    in the slabs for ``D ∪ {d'}`` (d' below min(D), ascending) at its own
    high positions before being added — reproducing, add for add, the IEEE
    tree the sequential sweep would have computed.
    """
    t = recv[D]
    for dp in [d for d in pdims if d < min(D)]:
        sub = _gather_tree(recv, pdims, tuple(sorted(set(D) | {dp})))
        ax = _axis(dp)
        idx = t.shape[ax] - 1
        sl = [slice(None)] * 3
        sl[ax] = slice(idx, idx + 1)
        t = t.at[tuple(sl)].add(sub)
    return t


def _broadcast_fused(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    wire_dtype: Any | None,
    coords,
) -> jax.Array:
    """One-round owner→replica refresh of every low face/edge/corner slab.

    The canonical copy of an interface DOF lives where it sits on the HIGH
    face in every partitioned dim that shares it; each nonempty dim subset
    D ships the owner's high slab diagonally to the +1_D neighbor, which
    writes it into its low slab — masked so a position only accepts the
    slab whose dim set matches its actual sharing pattern (low positions
    in dims outside D defer to the larger-D message unless they sit on the
    grid boundary there).  The masks make the write regions disjoint, so
    the message order is irrelevant.
    """
    pdims = _pdims(grid)
    for D in _subsets(pdims):
        spec_hi = {d: (box.shape[_axis(d)] - 1, box.shape[_axis(d)]) for d in D}
        hi = _slab(box, spec_hi)
        off = tuple(+1 if d in D else 0 for d in range(3))
        recv = _wire_permute(hi, axis_name, grid.offset_perm(off), wire_dtype)
        spec_lo = {d: (0, 1) for d in D}
        cur = _slab(box, spec_lo)
        valid = coords[D[0]] > 0
        for d in D[1:]:
            valid = valid & (coords[d] > 0)
        for d in pdims:
            if d in D:
                continue
            ax = _axis(d)
            shape = [1, 1, 1]
            shape[ax] = cur.shape[ax]
            pos = jnp.arange(cur.shape[ax]).reshape(shape)
            valid = valid & ((pos > 0) | (coords[d] == 0))
        box = box.at[_slab_sl(spec_lo)].set(jnp.where(valid, recv, cur))
    return box


def _round_hi_faces(
    box: jax.Array, grid: ProcessGrid, wire_dtype: Any | None
) -> jax.Array:
    """Round every partitioned high face to the wire dtype (owner side)."""
    if wire_dtype is None or jnp.dtype(wire_dtype) == box.dtype:
        return box
    for d in _pdims(grid):
        m = box.shape[_axis(d)]
        box = _set_face(box, d, m - 1, _wire_round(_face(box, d, m - 1), wire_dtype))
    return box


def _sum_fused(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    wire_dtype: Any | None,
) -> jax.Array:
    """All-dims-in-one-round sum_exchange: gather + masked broadcast.

    Gather round: every nonempty subset D of the partitioned dims ships
    the sender's low slab (face, edge or corner) straight to its -1_D
    diagonal neighbor — all messages concurrent, operating on the original
    box.  The receiver then rebuilds the sweep's accumulation order with
    :func:`_gather_tree` nested adds, so the high slabs end up holding the
    bit-identical canonical sums.  Broadcast round: the summed high slabs
    travel +1_D to refresh the replicas (masked per sharing pattern).
    Two dependent rounds total instead of the sweep's six.
    """
    pdims = _pdims(grid)
    if not pdims:
        return box
    coords = rank_coords(grid, axis_name)
    recv: dict[tuple[int, ...], jax.Array] = {}
    for D in _subsets(pdims):
        off = tuple(-1 if d in D else 0 for d in range(3))
        low = _slab(box, {d: (0, 1) for d in D})
        recv[D] = _wire_permute(low, axis_name, grid.offset_perm(off), wire_dtype)
    for d in pdims:
        m = box.shape[_axis(d)]
        box = _add_face(box, d, m - 1, _gather_tree(recv, pdims, (d,)))
    box = _round_hi_faces(box, grid, wire_dtype)
    return _broadcast_fused(box, grid, axis_name, wire_dtype, coords)


def _shell(box: jax.Array, dim: int, lo: int, hi: int) -> jax.Array:
    sl = [slice(None)] * 3
    sl[_axis(dim)] = slice(lo, hi)
    return box[tuple(sl)]


def _set_shell(box: jax.Array, dim: int, lo: int, hi: int, val) -> jax.Array:
    sl = [slice(None)] * 3
    sl[_axis(dim)] = slice(lo, hi)
    return box.at[tuple(sl)].set(val)


def _add_shell(box: jax.Array, dim: int, lo: int, hi: int, val) -> jax.Array:
    sl = [slice(None)] * 3
    sl[_axis(dim)] = slice(lo, hi)
    return box.at[tuple(sl)].add(val)


def _signed_subsets(
    pdims: list[int],
) -> list[tuple[tuple[int, ...], tuple[int, ...]]]:
    """(dim subset, sign vector) pairs — one per directed diagonal neighbor."""
    out = []
    for D in _subsets(pdims):
        for s in itertools.product((-1, +1), repeat=len(D)):
            out.append((D, s))
    return out


def _expand_fused(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    depth: int,
    wire_dtype: Any | None,
) -> jax.Array:
    """One-round expand: every shell region fills straight from its origin.

    The sweep routes edge/corner overlap data through intermediate ranks
    (pure copies at every hop); shipped directly instead, each directed
    diagonal neighbor sends its interior slab adjacent to the shared
    interface in one concurrent round (≤ 26 messages).  Copies are
    rounding-idempotent, so the result is bit-identical to the sweep even
    under a narrowed wire.
    """
    d = int(depth)
    if d == 0:
        return box
    pdims = _pdims(grid)
    box = jnp.pad(box, d)
    m = {dim: box.shape[_axis(dim)] for dim in range(3)}
    morig = {dim: m[dim] - 2 * d for dim in range(3)}
    for D, s in _signed_subsets(pdims):
        # receiver r's shell on side s_d of dim d comes from the rank at
        # r + sum(s_d * e_d); the permute therefore shifts by -s
        off = tuple(-s[D.index(dim)] if dim in D else 0 for dim in range(3))
        send: dict[int, tuple[int, int]] = {}
        write: dict[int, tuple[int, int]] = {}
        for dim, sd in zip(D, s):
            if sd == -1:
                # low shell <- sender's top interior (padded
                # [morig-1, morig-1+d), original [morig-1-d, morig-1))
                send[dim] = (morig[dim] - 1, morig[dim] - 1 + d)
                write[dim] = (0, d)
            else:
                # high shell <- sender's bottom interior (original [1, 1+d))
                send[dim] = (1 + d, 1 + 2 * d)
                write[dim] = (m[dim] - d, m[dim])
        for dim in range(3):
            if dim not in D:
                # original extent in the other dims: shell-of-shell slots
                # belong to larger-D regions (or stay zero)
                send[dim] = (d, m[dim] - d)
                write[dim] = (d, m[dim] - d)
        recv = _wire_permute(
            _slab(box, send), axis_name, grid.offset_perm(off), wire_dtype
        )
        box = box.at[_slab_sl(write)].set(recv)
    return box


@obs.scope("halo.expand")
def expand_exchange(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    depth: int,
    wire_dtype: Any | None = None,
    routing: str = "face_sweep",
) -> jax.Array:
    """Grow a consistent box by a ``depth``-node shell of neighbor data.

    The overlap transport of the Schwarz smoother: rank-boundary element
    blocks extend ``depth`` GLL node layers into neighbor ranks, so the
    (bz, by, bx)-shaped consistent box comes back as
    (bz+2d, by+2d, bx+2d) with shells holding the neighbors' interior
    slabs (their node layers just inside the shared interface).  Shells
    with no neighbor — unpartitioned dims and physical domain boundaries —
    stay zero (ppermute's zero-fill), matching the dummy slots of the
    extended-block FDM setup.

    The sequential dimension sweeps reuse the sum_exchange trick: the dim-1
    slab a neighbor sends already contains its dim-0 shell, so edge/corner
    overlap data propagates without explicit 26-neighbor messages.
    ``contract_exchange`` is the exact adjoint (same sweeps reversed).
    ``routing="fused"`` ships all ≤ 26 directed regions concurrently in
    one round instead (bit-identical — the hops are pure copies).
    """
    if routing == "fused":
        return _expand_fused(box, grid, axis_name, depth, wire_dtype)
    if routing != "face_sweep":
        raise ValueError(f"unknown expand_exchange routing: {routing!r}")
    d = int(depth)
    if d == 0:
        return box
    box = jnp.pad(box, d)
    for dim in range(3):
        if grid.shape[dim] == 1:
            continue
        ax = _axis(dim)
        m = box.shape[ax]          # padded length = original + 2d
        morig = m - 2 * d
        # low shell <- -neighbor's top interior slab (their original
        # indices [morig-1-d, morig-1) == padded [morig-1, morig-1+d))
        recv = _wire_permute(
            _shell(box, dim, morig - 1, morig - 1 + d),
            axis_name,
            grid.shift_perm(dim, +1),
            wire_dtype,
        )
        box = _set_shell(box, dim, 0, d, recv)
        # high shell <- +neighbor's bottom interior slab (their original
        # [1, 1+d) == padded [1+d, 1+2d))
        recv = _wire_permute(
            _shell(box, dim, 1 + d, 1 + 2 * d),
            axis_name,
            grid.shift_perm(dim, -1),
            wire_dtype,
        )
        box = _set_shell(box, dim, m - d, m, recv)
    return box


def _contract_tree(
    recv: dict, pdims: list[int], d: int, morig: dict[int, int],
    D: tuple[int, ...], s: tuple[int, ...],
) -> jax.Array:
    """Rebuild the reverse sweep's in-transit accumulation for one region.

    In the sweep (dims descending), a rank's dim-k shell accumulates
    arriving slabs from every LATER dim k' > k before shipping at stage k;
    delivered directly instead, the receiver folds the slab for
    ``(D ∪ {k'}, ·)`` into the slab for ``(D, s)`` at the k'-interior row
    positions, k' descending, + direction first — the exact add order the
    sequential sweep would have produced, so the result is bit-identical.
    """
    t = recv[(D, s)]
    for kp in sorted([k for k in pdims if k > max(D)], reverse=True):
        ax = _axis(kp)
        for sp in (+1, -1):
            sub = _contract_tree(
                recv, pdims, d, morig,
                tuple(sorted(set(D) | {kp})),
                _merge_sign(D, s, kp, sp),
            )
            # slab-local rows (the slab spans the original extent in kp,
            # i.e. padded offset d): +1 lands at the top interior
            # [morig-1, morig-1+d), -1 at the bottom [1+d, 1+2d)
            lo = (morig[kp] - 1 - d) if sp == +1 else 1
            sl = [slice(None)] * 3
            sl[ax] = slice(lo, lo + d)
            t = t.at[tuple(sl)].add(sub)
    return t


def _merge_sign(
    D: tuple[int, ...], s: tuple[int, ...], kp: int, sp: int
) -> tuple[int, ...]:
    """Sign vector for D ∪ {kp}, keeping dim order sorted."""
    pairs = sorted(zip(D, s)) + [(kp, sp)]
    pairs.sort()
    return tuple(sd for _, sd in pairs)


def _contract_fused(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    depth: int,
    wire_dtype: Any | None,
) -> jax.Array:
    """One-round contract: every shell region ships straight home.

    Adjoint of :func:`_expand_fused`: each directed shell region (face,
    edge, corner × side) travels to its owner in one concurrent round;
    the receiver then replays the reverse sweep's accumulation order with
    :func:`_contract_tree` nested adds, so the per-rank partial sums come
    out bit-identical to the sweep at the native wire.
    """
    d = int(depth)
    if d == 0:
        return box
    pdims = _pdims(grid)
    m = {dim: box.shape[_axis(dim)] for dim in range(3)}
    morig = {dim: m[dim] - 2 * d for dim in range(3)}
    recv: dict = {}
    for D, s in _signed_subsets(pdims):
        # recv is keyed by the ORIGIN direction s seen from the receiver:
        # the neighbor at +s ships its shell region on sides -s (its low
        # shell travels down, its high shell travels up), so the sender's
        # permute offset is -s
        off = tuple(-s[D.index(dim)] if dim in D else 0 for dim in range(3))
        spec: dict[int, tuple[int, int]] = {}
        for dim, sd in zip(D, s):
            spec[dim] = (0, d) if sd == +1 else (m[dim] - d, m[dim])
        for dim in range(3):
            if dim not in D:
                spec[dim] = (d, m[dim] - d)
        recv[(D, s)] = _wire_permute(
            _slab(box, spec), axis_name, grid.offset_perm(off), wire_dtype
        )
    # home-side adds replay the sweep's stage order: dims descending,
    # + direction (top interior) before - (bottom interior) — the two can
    # overlap on thin boxes (morig < 2d+2), where add order matters
    for k in sorted(pdims, reverse=True):
        ax = _axis(k)
        for sk in (+1, -1):
            t = _contract_tree(recv, pdims, d, morig, (k,), (sk,))
            lo = (morig[k] - 1) if sk == +1 else (1 + d)
            spec = {dim: (d, m[dim] - d) for dim in range(3)}
            spec[k] = (lo, lo + d)
            box = box.at[_slab_sl(spec)].add(t)
    return box[d:-d, d:-d, d:-d]


@obs.scope("halo.contract")
def contract_exchange(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    depth: int,
    wire_dtype: Any | None = None,
    routing: str = "face_sweep",
) -> jax.Array:
    """Adjoint of :func:`expand_exchange`: return shell contributions home.

    ``box`` is a (bz+2d, by+2d, bx+2d) accumulation of extended-block
    gather contributions; each shell slab belongs to a neighbor rank's
    interior and is shipped back and added there, then zeroed.  Dimensions
    run in reverse order so edge/corner contributions hop home
    dimension-by-dimension (the transpose of the expand sweeps).
    Contributions in never-filled shells (domain boundaries) correspond to
    dummy FDM slots and are discarded.  Returns the stripped
    (bz, by, bx) box of per-rank partial sums — interface *face* replicas
    still need the usual ``sum_exchange`` to become consistent.
    ``routing="fused"`` delivers all ≤ 26 directed regions home in one
    concurrent round, replaying the sweep's accumulation order locally
    (bit-identical at the native wire).
    """
    if routing == "fused":
        return _contract_fused(box, grid, axis_name, depth, wire_dtype)
    if routing != "face_sweep":
        raise ValueError(f"unknown contract_exchange routing: {routing!r}")
    d = int(depth)
    if d == 0:
        return box
    for dim in (2, 1, 0):
        ax = _axis(dim)
        m = box.shape[ax]
        morig = m - 2 * d
        if grid.shape[dim] > 1:
            # my low shell -> -neighbor's top interior ([morig-1, morig-1+d)
            # in their padded indexing); I receive the +neighbor's low shell
            recv = _wire_permute(
                _shell(box, dim, 0, d), axis_name,
                grid.shift_perm(dim, -1), wire_dtype,
            )
            box = _add_shell(box, dim, morig - 1, morig - 1 + d, recv)
            recv = _wire_permute(
                _shell(box, dim, m - d, m), axis_name,
                grid.shift_perm(dim, +1), wire_dtype,
            )
            box = _add_shell(box, dim, 1 + d, 1 + 2 * d, recv)
        zero = jnp.zeros_like(_shell(box, dim, 0, d))
        box = _set_shell(box, dim, 0, d, zero)
        box = _set_shell(box, dim, m - d, m, zero)
    return box[d:-d, d:-d, d:-d]


@obs.scope("halo.copy")
def copy_exchange(
    box: jax.Array,
    grid: ProcessGrid,
    axis_name: str,
    wire_dtype: Any | None = None,
    routing: str = "face_sweep",
) -> jax.Array:
    """Refresh replica slabs from owners (owner = low-side rank).

    The canonical copy of an interface point lives on the rank where it sits
    on the HIGH face of the padded box; the +neighbor's low-face replica is
    overwritten. This is hipBone's scatter-side halo exchange in isolation.
    With ``wire_dtype`` the owner's high face is rounded to the wire dtype
    too, so replicas and owner agree on the rounded value.

    ``routing="fused"`` replaces the three dependent per-dim rounds with
    the single masked diagonal broadcast round of the fused sum route —
    pure copies, so the result is bit-identical to the sweep even under a
    narrowed wire.  (There is no staged variant distinct from the sweep;
    ``comms.plan.resolve_routing`` maps "crystal" here to "face_sweep".)
    """
    if routing == "fused":
        coords = rank_coords(grid, axis_name)
        box = _round_hi_faces(box, grid, wire_dtype)
        return _broadcast_fused(box, grid, axis_name, wire_dtype, coords)
    if routing != "face_sweep":
        raise ValueError(f"unknown copy_exchange routing: {routing!r}")
    coords = rank_coords(grid, axis_name)
    for dim in range(3):
        pd = grid.shape[dim]
        if pd == 1:
            continue
        m = box.shape[_axis(dim)]
        c = coords[dim]
        hi = _wire_round(_face(box, dim, m - 1), wire_dtype)
        box = _set_face(box, dim, m - 1, hi)
        recv = _wire_permute(hi, axis_name, grid.shift_perm(dim, +1), wire_dtype)
        keep = _face(box, dim, 0)
        box = _set_face(box, dim, 0, jnp.where(c > 0, recv, keep))
    return box
