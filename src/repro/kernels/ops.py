"""Public jit'd wrappers for the Pallas kernels.

Handles padding to tile shapes, the CPU/TPU interpret switch, and the
selection policies. Everything downstream (core.operator, core.cg,
benchmarks) calls these, never pl.pallas_call directly.

``interpret`` left as None resolves through ``default_interpret()``: the
interpreter off-TPU, so the same code validates on CPU, and Mosaic on a
real TPU backend.  Native kernels take no float64 (``backend``); the
selection policies below never pick a kernel they cannot lower, and an
explicit request for one raises instead of degrading.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .. import obs
from .backend import default_interpret, native_dtype_ok
from .blocks import block_matvec_pallas, pick_block_matvec_e
from .poisson import pick_block_e, poisson_local_pallas
from .poisson_fused import (
    NO_NATIVE_LOWERING,
    pick_fused_block_e,
    poisson_assembled_fused_pallas,
)
from .streams import (
    DEFAULT_BLOCK_ROWS,
    LANES,
    fused_axpy_dot_batched_pallas,
    fused_axpy_dot_pallas,
    fused_cheb_d_update_pallas,
    fused_jacobi_dot_batched_pallas,
    fused_jacobi_dot_pallas,
    fused_xpay_batched_pallas,
    fused_xpay_pallas,
    weighted_dot_pallas,
)

__all__ = [
    "default_interpret",
    "fused_override",
    "should_fuse_streams",
    "should_fuse_operator",
    "poisson_local",
    "poisson_assembled_fused",
    "make_poisson_assembled_fused",
    "block_matvec",
    "make_block_matvec",
    "fused_axpy_dot",
    "fused_xpay",
    "weighted_dot",
    "fused_jacobi_dot",
    "fused_cheb_d_update",
    "fused_axpy_dot_batched",
    "fused_xpay_batched",
    "fused_jacobi_dot_batched",
    "make_local_op",
    "make_fused_jacobi_dot",
    "make_fused_cheb_d_update",
    "make_fused_jacobi_dot_batched",
]


def fused_override() -> bool | None:
    """The HIPBONE_FUSED env override shared by every auto-enable policy.

    "0" forces the fused paths off, "1" forces them on even off-TPU (the
    CI pallas-interpret job routes the whole test suite through the
    interpret-mode kernels this way); anything else defers to the
    per-policy auto rule.
    """
    env = os.environ.get("HIPBONE_FUSED", "")
    if env in ("0", "1"):
        return env == "1"
    return None


def should_fuse_streams(dtype) -> bool:
    """Auto-enable policy for the fused streaming stages in solver hot paths.

    True when Pallas compiles natively (non-interpret backend, i.e. real
    TPU — interpret mode makes the fusions *slower* on CPU) AND the
    vectors the stage streams are fp32: the kernels' scalar reductions
    accumulate in fp32, which is exact enough for fp32 solves and for the
    fp32 interior of a mixed-precision preconditioner, but would throw away
    bits an fp64 tol=1e-8 recurrence needs.  ``HIPBONE_FUSED``
    (``fused_override``) wins over the auto rule, except that float64 is
    never fused on a native backend (Mosaic has no float64); callers keep
    an explicit opt-out knob on top of this.
    """
    native = not default_interpret()
    if native and not native_dtype_ok(dtype):
        return False
    ov = fused_override()
    if ov is not None:
        return ov
    return native and jnp.dtype(dtype) == jnp.float32


def should_fuse_operator() -> bool:
    """Auto-enable policy for the single-kernel fused assembled operator.

    Off unless ``HIPBONE_FUSED=1`` asks for it.  The rule: the kernel has
    no native lowering (``kernels.poisson_fused``: Mosaic has no per-lane
    VMEM gather/scatter), and through the interpreter it is slower than
    XLA's split scatter→local-op→gather pipeline, so the split path is
    the operator on every backend — float64 included.  An explicit
    request on a native backend (``fused=True`` or ``HIPBONE_FUSED=1``)
    reaches ``poisson_assembled_fused`` and raises there.
    """
    return fused_override() is True


def _pad_rows(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, n


def poisson_local(
    u: jax.Array,
    g: jax.Array,
    w: jax.Array | None,
    d: jax.Array,
    *,
    lam: float,
    block_e: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused (S_L + λW) u with element padding. See kernels/poisson.py."""
    e = u.shape[0]
    n1 = d.shape[0]
    eb = block_e or pick_block_e(n1 - 1, u.dtype)
    eb = max(1, min(eb, e))
    if w is None:
        w = jnp.ones_like(u)
    u_p, _ = _pad_rows(u, eb)
    g_p, _ = _pad_rows(g, eb)
    w_p, _ = _pad_rows(w, eb)
    out = poisson_local_pallas(
        u_p, g_p, w_p, d, lam=lam, block_e=eb, interpret=interpret
    )
    return out[:e]


def poisson_assembled_fused(
    x_g: jax.Array,
    l2g: jax.Array,
    g: jax.Array,
    w: jax.Array,
    d: jax.Array,
    *,
    lam: float,
    block_e: int | None = None,
    interpret: bool | None = None,
    gather_mode: str = "take",
) -> jax.Array:
    """Single-pass y_G = Z^T (S_L + λW) Z x_G with padding handled.

    The array-level fused assembled apply (kernels/poisson_fused.py): pads
    x_G to the 128-lane tile and the element streams to ``block_e``, points
    padded elements at slot 0 (their zero G/W contributes exactly 0.0), and
    slices the result back to (n_global,).  Matches
    ``core.operator.poisson_assembled`` to summation-order round-off.
    """
    n_g = x_g.shape[0]
    e = l2g.shape[0]
    n1 = d.shape[0]
    eb = block_e or pick_fused_block_e(n1 - 1, n_g, x_g.dtype)
    eb = max(1, min(eb, max(e, 1)))
    x2 = _pad_to(x_g, -(-n_g // LANES) * LANES).reshape(-1, LANES)
    l2g_p, _ = _pad_rows(l2g.astype(jnp.int32), eb)
    g_p, _ = _pad_rows(g, eb)
    w_p, _ = _pad_rows(w, eb)
    y2 = poisson_assembled_fused_pallas(
        x2,
        l2g_p,
        g_p,
        w_p,
        d,
        lam=float(lam),
        block_e=eb,
        interpret=interpret,
        gather_mode=gather_mode,
    )
    return y2.reshape(-1)[:n_g]


def make_poisson_assembled_fused(
    prob,
    *,
    block_e: int | None = None,
    interpret: bool | None = None,
    gather_mode: str = "take",
):
    """Fused-operator apply closure for a ``core.operator.PoissonProblem``.

    Same call signature as the split ``poisson_assembled(prob)`` result —
    x_G -> A x_G — so the two are drop-in interchangeable; the returned
    closure carries ``apply.fused = True`` for introspection.

    Variable-coefficient problems need no kernel changes: k(x) is already
    folded into ``prob.g`` and the λ(x) screen rides the ``w`` stream with
    ``lam`` pinned to 1.0 (``core.operator.screen_stream`` — ``lam`` is a
    static argname in the Pallas jit, which is exactly why the field form
    cannot go through it); Dirichlet BCs are the same mask∘A∘mask wrap as
    the split path.
    """
    from ..core.operator import screen_stream  # lazy: core imports kernels

    if not (default_interpret() if interpret is None else interpret):
        raise NotImplementedError(NO_NATIVE_LOWERING)
    w_eff, lam_eff = screen_stream(prob)
    mask = prob.mask

    @obs.scope("op.fused")
    def apply(x_g: jax.Array) -> jax.Array:
        if mask is not None:
            x_g = mask * x_g
        y_g = poisson_assembled_fused(
            x_g,
            prob.l2g,
            prob.g,
            w_eff,
            prob.d,
            lam=lam_eff,
            block_e=block_e,
            interpret=interpret,
            gather_mode=gather_mode,
        )
        return y_g if mask is None else mask * y_g

    apply.fused = True
    apply.assembly = "indexed"  # in-kernel gather and scatter-add
    return apply


def block_matvec(
    blocks: jax.Array,
    u: jax.Array,
    *,
    block_e: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched dense element matvec y_e = B_e u_e with element padding.

    The Pallas form of the materialized-Galerkin coarse apply
    (``core.galerkin.block_matvec_einsum`` is the XLA reference); see
    kernels/blocks.py.  Shapes: (E, p, p), (E, p) -> (E, p).
    """
    e, p = u.shape
    eb = block_e or pick_block_matvec_e(p, u.dtype)
    eb = max(1, min(eb, e))
    b_p, _ = _pad_rows(blocks, eb)
    u_p, _ = _pad_rows(u, eb)
    out = block_matvec_pallas(b_p, u_p, block_e=eb, interpret=interpret)
    return out[:e]


def make_block_matvec(*, block_e: int | None = None, interpret: bool | None = None):
    """Adapter with core.galerkin's ``matvec`` signature (blocks, u) -> y."""
    return lambda blocks, u: block_matvec(
        blocks, u, block_e=block_e, interpret=interpret
    )


def stream_tiling(n: int, want: int = DEFAULT_BLOCK_ROWS) -> tuple[int, int]:
    """(padded length, block rows) for streaming an n-vector as (rows, 128).

    Mosaic tiles f32 as (8, 128), so a block's row count must be a
    multiple of 8.  The rows split into ceil(rows / want) equal blocks,
    each rounded up to 8 rows: the padding stays under 8 rows per block
    instead of falling to 1-row blocks when the row count has no suitable
    divisor (57³ DOFs -> 1447 rows -> 3 blocks of 488).
    """
    rows = max(-(-n // LANES), 1)
    nblk = -(-rows // want)
    br = -(-rows // nblk)
    br += (-br) % 8
    return nblk * br * LANES, br


def _pad_to(x: jax.Array, size: int) -> jax.Array:
    """Zero-pad the trailing axis of x to ``size``."""
    pad = size - x.shape[-1]
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1
        )
    return x


def fused_axpy_dot(
    r: jax.Array, ap: jax.Array, alpha: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """One-pass (r - α·Ap, ||r - α·Ap||²) for arbitrary-length vectors."""
    shape, n = r.shape, r.size
    size, br = stream_tiling(n)
    r_new, rr = fused_axpy_dot_pallas(
        _pad_to(r.reshape(-1), size), _pad_to(ap.reshape(-1), size), alpha,
        block_rows=br, interpret=interpret,
    )
    # padded tail contributes alpha*0 - 0 = 0 to both outputs
    return r_new[:n].reshape(shape), rr


def fused_xpay(
    r: jax.Array, p: jax.Array, beta: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    shape, n = r.shape, r.size
    size, br = stream_tiling(n)
    out = fused_xpay_pallas(
        _pad_to(r.reshape(-1), size), _pad_to(p.reshape(-1), size), beta,
        block_rows=br, interpret=interpret,
    )
    return out[:n].reshape(shape)


def weighted_dot(
    w: jax.Array, a: jax.Array, b: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    size, br = stream_tiling(w.size)
    w_p, a_p, b_p = (_pad_to(x.reshape(-1), size) for x in (w, a, b))
    return weighted_dot_pallas(w_p, a_p, b_p, block_rows=br, interpret=interpret)


def fused_jacobi_dot(
    dinv: jax.Array, r: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """One-pass (D⁻¹r, r·D⁻¹r) for arbitrary-length vectors (PCG z-stage)."""
    shape, n = r.shape, r.size
    size, br = stream_tiling(n)
    # padded tail: dinv pad is 0 so z and the r·z partials stay 0 there
    z, rz = fused_jacobi_dot_pallas(
        _pad_to(dinv.reshape(-1), size), _pad_to(r.reshape(-1), size),
        block_rows=br, interpret=interpret,
    )
    return z[:n].reshape(shape), rz


def fused_cheb_d_update(
    a: jax.Array,
    c: jax.Array,
    d: jax.Array,
    r: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """d ← a·d + c·r for arbitrary-length vectors (Chebyshev d-update)."""
    shape, n = d.shape, d.size
    size, br = stream_tiling(n)
    out = fused_cheb_d_update_pallas(
        a, c, _pad_to(d.reshape(-1), size), _pad_to(r.reshape(-1), size),
        block_rows=br, interpret=interpret,
    )
    return out[:n].reshape(shape)


def fused_axpy_dot_batched(
    r: jax.Array, ap: jax.Array, alpha: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Per-column one-pass (r - α·Ap, ‖·‖²) over a (B, n) RHS block.

    ``alpha`` is (B,) — each solve column advances by its own CG step.
    Returns the updated (B, n) block and the (B,) squared norms.
    """
    n = r.shape[-1]
    size, br = stream_tiling(n)
    # padded tail contributes alpha*0 - 0 = 0 to both outputs
    r_new, rr = fused_axpy_dot_batched_pallas(
        _pad_to(r, size), _pad_to(ap, size), alpha,
        block_rows=br, interpret=interpret,
    )
    return r_new[:, :n], rr


def fused_xpay_batched(
    r: jax.Array, p: jax.Array, beta: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """Per-column r + β·p over a (B, n) block; ``beta`` is (B,)."""
    n = r.shape[-1]
    size, br = stream_tiling(n)
    out = fused_xpay_batched_pallas(
        _pad_to(r, size), _pad_to(p, size), beta,
        block_rows=br, interpret=interpret,
    )
    return out[:, :n]


def fused_jacobi_dot_batched(
    dinv: jax.Array, r: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Per-column (D⁻¹r, r·D⁻¹r) over a (B, n) block.

    ``dinv`` stays (n,) — the diagonal stream is shared by every column,
    never replicated B-fold through memory.
    """
    n = r.shape[-1]
    size, br = stream_tiling(n)
    # padded tail: dinv pad is 0 so z and the r·z partials stay 0 there
    z, rz = fused_jacobi_dot_batched_pallas(
        _pad_to(dinv, size), _pad_to(r, size),
        block_rows=br, interpret=interpret,
    )
    return z[:, :n], rz


def make_fused_jacobi_dot_batched(
    dinv: jax.Array, *, interpret: bool | None = None, out_dtype=None
):
    """Batched counterpart of ``make_fused_jacobi_dot``: r_block -> (z, r·z).

    Same mixed-precision boundary: with ``out_dtype`` the (B, n) block is
    rounded to ``dinv.dtype`` for the fused pass and widened back.
    """
    if out_dtype is None:
        return lambda r: fused_jacobi_dot_batched(dinv, r, interpret=interpret)
    odt = jnp.dtype(out_dtype)

    def apply(r: jax.Array) -> tuple[jax.Array, jax.Array]:
        z, rz = fused_jacobi_dot_batched(
            dinv, r.astype(dinv.dtype), interpret=interpret
        )
        return z.astype(odt), rz.astype(odt)

    return apply


def make_fused_jacobi_dot(
    dinv: jax.Array, *, interpret: bool | None = None, out_dtype=None
):
    """Adapter with cg_assembled's fused_precond_dot signature r -> (z, r·z).

    ``out_dtype`` is the mixed-precision boundary: r is rounded to
    ``dinv.dtype`` before the fused pass and (z, r·z) widened back, so an
    fp32 fused Jacobi stage (fp32 dinv) can gate an fp64 outer PCG — the
    fp32-input variant of the stage the mixed path uses.
    """
    if out_dtype is None:
        return lambda r: fused_jacobi_dot(dinv, r, interpret=interpret)
    odt = jnp.dtype(out_dtype)

    def apply(r: jax.Array) -> tuple[jax.Array, jax.Array]:
        z, rz = fused_jacobi_dot(
            dinv, r.astype(dinv.dtype), interpret=interpret
        )
        return z.astype(odt), rz.astype(odt)

    return apply


def make_fused_cheb_d_update(*, interpret: bool | None = None):
    """Adapter with chebyshev_apply's fused_d_update signature (a, c, d, r)."""
    return lambda a, c, d, r: fused_cheb_d_update(a, c, d, r, interpret=interpret)


def make_local_op(*, block_e: int | None = None, interpret: bool | None = None):
    """Adapter with core.operator's local_op signature (u, g, d, lam, w)."""

    def op(u, g, d, lam, w, jw=None):
        del jw
        return poisson_local(
            u, g, w, d, lam=float(lam), block_e=block_e, interpret=interpret
        )

    return op
