"""Pallas TPU streaming kernels for the CG vector operations.

The paper's CG-iteration optimizations are streaming fusions:
  * ``fused_axpy_dot``:  r_new = r - α·Ap  AND  Σ r_new²  in ONE pass —
    "Fusing this reduction with the update of r avoids the need for a
    separate kernel to read the vector r again."
  * ``fused_xpay``:      p = r + β·p  (the CG direction update).
  * ``weighted_dot``:    Σ w·a·b — NekBone-baseline weighted inner product
    (reads the extra weight stream, as the paper charges it).
  * ``fused_jacobi_dot``: z = D⁻¹r  AND  Σ r·z in ONE pass — the same
    streaming trick applied to the PCG preconditioner stage (the z vector
    is produced and the r·z reduction taken without re-reading r).
  * ``fused_cheb_d_update``: d = a·d + c·(D⁻¹·res) — the Chebyshev–Jacobi
    direction update with the Jacobi scale folded in (three streams, two
    SMEM scalars, one pass).

TPU mapping: 1-D vectors are viewed as (rows, 128) lane tiles; the grid
walks row blocks whose row count is a multiple of 8 (``kernels.ops`` pads
every vector to whole (8k)x128 tiles, Mosaic's f32 tile); scalar reductions accumulate into a (1, 1) output block
that every grid step revisits (TPU grids are sequential, so the
accumulation is deterministic — unlike GPU atomics). α/β arrive as (1, 1)
SMEM scalars so the same compiled kernel serves every iteration.

Batched (multi-RHS) layouts: the ``*_batched`` variants take a
``(B, rows, 128)`` block of vectors — the leading-batch-dim layout of the
batched PCG (``core.cg.batched_cg_assembled``) — on a ``(B, row-blocks)``
grid.  Per-column scalars (α per RHS, the Σ reductions) become ``(B,)``
vectors: α/β ride in SMEM as a ``(B, 1)`` table indexed by the batch grid
axis, and each batch row accumulates into its own entry of a ``(B, 1)``
SMEM output table.  Shared streams (the Jacobi diagonal) keep a
single copy indexed only by the row-block axis, so the batch never
materializes B copies of per-problem state — the per-batch-seed idiom of
the pie ``rand_mv`` kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import pallas_call, resolve_interpret

__all__ = [
    "fused_axpy_dot_pallas",
    "fused_axpy_dot_batched_pallas",
    "fused_xpay_pallas",
    "fused_xpay_batched_pallas",
    "weighted_dot_pallas",
    "fused_jacobi_dot_pallas",
    "fused_jacobi_dot_batched_pallas",
    "fused_cheb_d_update_pallas",
]

LANES = 128
DEFAULT_BLOCK_ROWS = 512  # 512x128 f32 tile = 256 KB per stream


def _axpy_dot_kernel(alpha_ref, r_ref, ap_ref, rnew_ref, acc_ref):
    i = pl.program_id(0)
    alpha = alpha_ref[0, 0]
    r = r_ref[...]
    ap = ap_ref[...]
    r_new = r - alpha * ap
    rnew_ref[...] = r_new
    # explicit f32 (not weak-typed literals): see _jacobi_dot_kernel
    part = jnp.sum(
        r_new.astype(jnp.float32) * r_new.astype(jnp.float32)
    ).astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        acc_ref[0, 0] = jnp.float32(0.0)

    acc_ref[0, 0] += part


def _xpay_kernel(beta_ref, r_ref, p_ref, out_ref):
    beta = beta_ref[0, 0]
    out_ref[...] = r_ref[...] + beta * p_ref[...]


def _wdot_kernel(w_ref, a_ref, b_ref, acc_ref):
    i = pl.program_id(0)
    part = jnp.sum(
        w_ref[...].astype(jnp.float32)
        * a_ref[...].astype(jnp.float32)
        * b_ref[...].astype(jnp.float32)
    ).astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        acc_ref[0, 0] = jnp.float32(0.0)

    acc_ref[0, 0] += part


def _jacobi_dot_kernel(dinv_ref, r_ref, z_ref, acc_ref):
    i = pl.program_id(0)
    r = r_ref[...]
    z = dinv_ref[...] * r
    z_ref[...] = z
    # explicit f32 throughout: weak-typed literals would become f64 when the
    # host process runs with jax_enable_x64 (interpret-mode discharge does
    # not weak-cast stores)
    part = jnp.sum(r.astype(jnp.float32) * z.astype(jnp.float32)).astype(
        jnp.float32
    )

    @pl.when(i == 0)
    def _init():
        acc_ref[0, 0] = jnp.float32(0.0)

    acc_ref[0, 0] += part


def _cheb_d_kernel(a_ref, c_ref, d_ref, r_ref, out_ref):
    a = a_ref[0, 0]
    c = c_ref[0, 0]
    out_ref[...] = a * d_ref[...] + c * r_ref[...]


def _axpy_dot_batched_kernel(alpha_ref, r_ref, ap_ref, rnew_ref, acc_ref):
    b = pl.program_id(0)
    i = pl.program_id(1)
    alpha = alpha_ref[b, 0]
    r = r_ref[...]
    ap = ap_ref[...]
    r_new = r - alpha * ap
    rnew_ref[...] = r_new
    part = jnp.sum(
        r_new.astype(jnp.float32) * r_new.astype(jnp.float32)
    ).astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        acc_ref[b, 0] = jnp.float32(0.0)

    acc_ref[b, 0] += part


def _jacobi_dot_batched_kernel(dinv_ref, r_ref, z_ref, acc_ref):
    b = pl.program_id(0)
    i = pl.program_id(1)
    r = r_ref[...]
    # dinv is the SHARED per-problem stream: one (br, LANES) block serves
    # every batch row (broadcast against the (1, br, LANES) r block)
    z = dinv_ref[...][None, :, :] * r
    z_ref[...] = z
    part = jnp.sum(r.astype(jnp.float32) * z.astype(jnp.float32)).astype(
        jnp.float32
    )

    @pl.when(i == 0)
    def _init():
        acc_ref[b, 0] = jnp.float32(0.0)

    acc_ref[b, 0] += part


def _xpay_batched_kernel(beta_ref, r_ref, p_ref, out_ref):
    b = pl.program_id(0)
    out_ref[...] = r_ref[...] + beta_ref[b, 0] * p_ref[...]


def _as_tiles(x: jax.Array) -> jax.Array:
    """View a (rows*LANES,) vector as (rows, LANES); caller pre-pads."""
    return x.reshape(-1, LANES)


def _as_batched_tiles(x: jax.Array) -> jax.Array:
    """View a (B, rows*LANES) block as (B, rows, LANES); caller pre-pads."""
    return x.reshape(x.shape[0], -1, LANES)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_axpy_dot_pallas(
    r: jax.Array,
    ap: jax.Array,
    alpha: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(r - α·Ap, Σ(r - α·Ap)²) in one pass. r, ap: (rows, 128) tiles."""
    interpret = resolve_interpret(interpret, r.dtype, ap.dtype, kernel="fused_axpy_dot")
    r2, ap2 = _as_tiles(r), _as_tiles(ap)
    rows = r2.shape[0]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not a multiple of block_rows={br}")
    alpha2 = jnp.asarray(alpha, r2.dtype).reshape(1, 1)
    grid = (rows // br,)
    r_new, acc = pallas_call(
        _axpy_dot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(r2.shape, r2.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_axpy_dot",
    )(alpha2, r2, ap2)
    return r_new.reshape(r.shape), acc[0, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_xpay_pallas(
    r: jax.Array,
    p: jax.Array,
    beta: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """r + β·p, one pass."""
    interpret = resolve_interpret(interpret, r.dtype, p.dtype, kernel="fused_xpay")
    r2, p2 = _as_tiles(r), _as_tiles(p)
    rows = r2.shape[0]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not a multiple of block_rows={br}")
    beta2 = jnp.asarray(beta, r2.dtype).reshape(1, 1)
    out = pallas_call(
        _xpay_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(r2.shape, r2.dtype),
        interpret=interpret,
        name="fused_xpay",
    )(beta2, r2, p2)
    return out.reshape(r.shape)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def weighted_dot_pallas(
    w: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Σ w·a·b — NekBone's weighted inner product (extra weight stream)."""
    interpret = resolve_interpret(
        interpret, w.dtype, a.dtype, b.dtype, kernel="weighted_dot"
    )
    w2, a2, b2 = _as_tiles(w), _as_tiles(a), _as_tiles(b)
    rows = w2.shape[0]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not a multiple of block_rows={br}")
    acc = pallas_call(
        _wdot_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
        name="weighted_dot",
    )(w2, a2, b2)
    return acc[0, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_jacobi_dot_pallas(
    dinv: jax.Array,
    r: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(D⁻¹r, Σ r·D⁻¹r) in one pass — the PCG preconditioner-stage fusion."""
    interpret = resolve_interpret(
        interpret, dinv.dtype, r.dtype, kernel="fused_jacobi_dot"
    )
    d2, r2 = _as_tiles(dinv), _as_tiles(r)
    rows = r2.shape[0]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not a multiple of block_rows={br}")
    z, acc = pallas_call(
        _jacobi_dot_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(r2.shape, r2.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_jacobi_dot",
    )(d2, r2)
    return z.reshape(r.shape), acc[0, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_axpy_dot_batched_pallas(
    r: jax.Array,
    ap: jax.Array,
    alpha: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched (r - α·Ap, Σ(r - α·Ap)²): one pass over a (B, rows, 128) block.

    ``r``/``ap``: (B, rows*128) RHS blocks; ``alpha``: (B,) per-column CG
    step sizes (an SMEM table indexed by the batch grid axis).  Returns the
    updated (B, rows*128) block and the (B,) per-column reductions.
    """
    interpret = resolve_interpret(
        interpret, r.dtype, ap.dtype, kernel="fused_axpy_dot_batched"
    )
    r3, ap3 = _as_batched_tiles(r), _as_batched_tiles(ap)
    nb, rows = r3.shape[0], r3.shape[1]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not a multiple of block_rows={br}")
    alpha2 = jnp.asarray(alpha, r3.dtype).reshape(nb, 1)
    grid = (nb, rows // br)
    r_new, acc = pallas_call(
        _axpy_dot_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, br, LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, br, LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, br, LANES), lambda b, i: (b, i, 0)),
            # whole (B, 1) table in SMEM: a (1, 1) block of it is not a
            # legal tile, and each batch row owns its own entry
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(r3.shape, r3.dtype),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_axpy_dot_batched",
    )(alpha2, r3, ap3)
    return r_new.reshape(r.shape), acc[:, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_jacobi_dot_batched_pallas(
    dinv: jax.Array,
    r: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched (D⁻¹r, Σ r·D⁻¹r) over a (B, rows, 128) block, one pass.

    ``dinv``: (rows*128,) — the ONE shared diagonal stream, never
    replicated per column; ``r``: (B, rows*128).  Returns the (B, rows*128)
    z block and the (B,) per-column r·z reductions.
    """
    interpret = resolve_interpret(
        interpret, dinv.dtype, r.dtype, kernel="fused_jacobi_dot_batched"
    )
    d2, r3 = _as_tiles(dinv), _as_batched_tiles(r)
    nb, rows = r3.shape[0], r3.shape[1]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not a multiple of block_rows={br}")
    z, acc = pallas_call(
        _jacobi_dot_batched_kernel,
        grid=(nb, rows // br),
        in_specs=[
            pl.BlockSpec((br, LANES), lambda b, i: (i, 0)),
            pl.BlockSpec((1, br, LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, br, LANES), lambda b, i: (b, i, 0)),
            # whole (B, 1) table in SMEM: a (1, 1) block of it is not a
            # legal tile, and each batch row owns its own entry
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(r3.shape, r3.dtype),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_jacobi_dot_batched",
    )(d2, r3)
    return z.reshape(r.shape), acc[:, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_xpay_batched_pallas(
    r: jax.Array,
    p: jax.Array,
    beta: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched r + β·p over a (B, rows, 128) block; β: (B,) SMEM table."""
    interpret = resolve_interpret(
        interpret, r.dtype, p.dtype, kernel="fused_xpay_batched"
    )
    r3, p3 = _as_batched_tiles(r), _as_batched_tiles(p)
    nb, rows = r3.shape[0], r3.shape[1]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not a multiple of block_rows={br}")
    beta2 = jnp.asarray(beta, r3.dtype).reshape(nb, 1)
    out = pallas_call(
        _xpay_batched_kernel,
        grid=(nb, rows // br),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, br, LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, br, LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, br, LANES), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(r3.shape, r3.dtype),
        interpret=interpret,
        name="fused_xpay_batched",
    )(beta2, r3, p3)
    return out.reshape(r.shape)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_cheb_d_update_pallas(
    a: jax.Array,
    c: jax.Array,
    d: jax.Array,
    r: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """d ← a·d + c·r, one pass (Chebyshev direction update; two SMEM scalars)."""
    interpret = resolve_interpret(
        interpret, d.dtype, r.dtype, kernel="fused_cheb_d_update"
    )
    d2, r2 = _as_tiles(d), _as_tiles(r)
    rows = d2.shape[0]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not a multiple of block_rows={br}")
    a2 = jnp.asarray(a, d2.dtype).reshape(1, 1)
    c2 = jnp.asarray(c, d2.dtype).reshape(1, 1)
    out = pallas_call(
        _cheb_d_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(d2.shape, d2.dtype),
        interpret=interpret,
        name="fused_cheb_d_update",
    )(a2, c2, d2, r2)
    return out.reshape(d.shape)
