"""Single-pass Pallas kernel for the full assembled apply  y_G = Z^T (S_L + λW) Z x_G.

The split pipeline (core/operator.py ``poisson_assembled``) runs three XLA
ops — scatter Z, the element-local kernel, gather Z^T — and therefore
materializes x_L and y_L through HBM between stages. This kernel fuses all
three into one grid sweep over element blocks, so the seven input streams
(x_G, the l2g index map, the six geometric-factor planes + W) are each read
exactly once per CG iteration and y_L never exists:

  * x_G stays VMEM-resident across the whole grid (constant-index-map block
    — Mosaic fetches it once), viewed as (rows, 128) lane tiles;
  * per grid step, the (block_e, p) tile of ``l2g`` indices streams in and
    drives the in-kernel gather of the element-local field Z x_G;
  * the existing three-contraction MXU body from kernels/poisson.py
    (``local_body``) produces (S_L + λW) on the gathered block;
  * the scatter-add Z^T accumulates into a y_G output block that every
    sequential grid step revisits (``@pl.when(i == 0)`` zero-init, ``+=``
    per step) — TPU grids are serialized, so the accumulation is
    deterministic without atomics.

Native lowering: none.  Mosaic has no per-lane VMEM gather
(``jnp.take`` -> "Only 2D gather is supported") and refuses the scatter
behind the loop form's ``.at[k].set``; scalar-prefetching the whole l2g
map would also overflow SMEM at deployment sizes.  The kernel therefore
runs only through the Pallas interpreter, ``ops.should_fuse_operator``
never selects it on a native backend, and asking for it there raises
(``poisson_assembled_fused_pallas``).

Two gather/scatter strategies, selected by ``gather_mode``:

  * ``"take"`` (default): vectorized ``jnp.take`` / ``.at[].add`` on the
    VMEM-resident x_G/y_G blocks — the fast path wherever the backend
    supports lane gather (the interpret path CI validates on CPU).
  * ``"loop"``: the l2g map rides a ``PrefetchScalarGridSpec`` scalar-
    prefetch argument (SMEM), and gather/scatter run as a serial
    ``fori_loop`` of single-node dynamic slices. Slow but bit-compatible
    up to summation order; duplicates within a block are handled by the
    serial read-modify-write.

VMEM budget: unlike the element-local kernel, x_G and y_G are resident;
``pick_fused_block_e`` sizes the element block around them. Padding (elements to block_e, DOFs to the 128-lane tile) is handled by
``ops.poisson_assembled_fused``; padded elements carry zero G/W so they
contribute exactly 0.0 wherever their dummy index points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import pallas_call, resolve_interpret
from .poisson import body_scratch, derivative_factors, local_body, pick_block_e
from .streams import LANES

__all__ = [
    "poisson_assembled_fused_pallas",
    "fused_vmem_bytes",
    "pick_fused_block_e",
]

FUSED_VMEM_BUDGET = 8 * 2**20
NO_NATIVE_LOWERING = (
    "the fused assembled operator has no native Pallas lowering (Mosaic has "
    "no per-lane VMEM gather/scatter); use the split operator "
    "(poisson_assembled(..., fused=False), optionally with "
    "kernels.ops.make_local_op()) on this backend"
)


def fused_vmem_bytes(block_e: int, n1: int, n_pad: int, dtype=jnp.float32) -> int:
    """Estimated VMEM working set: resident x_G/y_G + one grid step's tiles."""
    p = n1**3
    word = jnp.dtype(dtype).itemsize
    acc = jnp.promote_types(jnp.dtype(dtype), jnp.float32).itemsize
    resident = 2 * n_pad * word  # x_G + y_G, pinned across the grid
    tiles = block_e * p * (4 + 7 * word)  # l2g (int32) + 6 G planes + W
    temps = block_e * p * 8 * acc  # u, ur/us/ut, wr/ws/wt, out
    return resident + tiles + temps


def pick_fused_block_e(
    n_degree: int,
    n_global: int,
    dtype=jnp.float32,
    budget_bytes: int = FUSED_VMEM_BUDGET,
) -> int:
    """Largest power-of-two element block fitting the budget with x/y resident."""
    n1 = n_degree + 1
    n_pad = -(-max(n_global, 1) // LANES) * LANES
    eb = min(256, pick_block_e(n_degree, dtype))
    while eb > 1 and fused_vmem_bytes(eb, n1, n_pad, dtype) > budget_bytes:
        eb //= 2
    return eb


def _kernel_take(
    idx_ref, x_ref, g_ref, w_ref, kf_ref, d_ref, y_ref, u_scr, *scratch, lam
):
    """One grid step, vector gather/scatter on the resident x/y blocks."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    idx = idx_ref[...].reshape(-1)  # (Eb*p,) int32
    x = x_ref[...].reshape(-1)  # (rows*128,) resident x_G
    eb, p = idx_ref.shape
    u_scr[...] = jnp.take(x, idx, axis=0).reshape(eb, p)  # gather Z x_G
    local_body(u_scr, g_ref, w_ref, kf_ref, d_ref, *scratch, lam=lam)
    y_l = scratch[0][...]
    # scatter-add Z^T into the revisited y_G block; duplicate indices within
    # the tile accumulate correctly through the segment-style .at[].add
    delta = jnp.zeros(x.shape, y_ref.dtype).at[idx].add(
        y_l.reshape(-1).astype(y_ref.dtype)
    )
    y_ref[...] += delta.reshape(y_ref.shape)


def _kernel_loop(
    idx_ref, x_ref, g_ref, w_ref, kf_ref, d_ref, y_ref, u_scr, *scratch, lam
):
    """One grid step, serial dynamic-slice gather/scatter (no lane gather).

    ``idx_ref`` is the scalar-prefetched full (E_pad*p,) l2g map in SMEM.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    eb, p = g_ref.shape[0], g_ref.shape[2]
    total = eb * p
    base = i * total

    def gather_one(k, u_flat):
        node = idx_ref[base + k]
        val = x_ref[node // LANES, node % LANES]
        return u_flat.at[k].set(val)

    u_scr[...] = jax.lax.fori_loop(
        0, total, gather_one, jnp.zeros((total,), x_ref.dtype)
    ).reshape(eb, p)
    local_body(u_scr, g_ref, w_ref, kf_ref, d_ref, *scratch, lam=lam)
    y_flat = scratch[0][...].reshape(-1).astype(y_ref.dtype)

    def scatter_one(k, carry):
        node = idx_ref[base + k]
        r, c = node // LANES, node % LANES
        y_ref[r, c] = y_ref[r, c] + y_flat[k]
        return carry

    jax.lax.fori_loop(0, total, scatter_one, 0)


@functools.partial(
    jax.jit,
    static_argnames=("lam", "block_e", "interpret", "gather_mode"),
)
def poisson_assembled_fused_pallas(
    x2: jax.Array,
    l2g: jax.Array,
    g: jax.Array,
    w: jax.Array,
    d: jax.Array,
    *,
    lam: float,
    block_e: int,
    interpret: bool | None = None,
    gather_mode: str = "take",
) -> jax.Array:
    """Single-kernel y_G = Z^T (S_L + λW) Z x_G on pre-padded operands.

    Args:
      x2: (rows, 128) lane-tiled padded x_G (zeros beyond n_global).
      l2g: (E, p) int32 local-to-global map into the flattened x2; E must be
        a multiple of block_e (ops.poisson_assembled_fused pads, pointing
        padded elements at slot 0 — their zero G/W makes that a no-op).
      g / w / d / lam: as in kernels/poisson.py.
      block_e: elements per grid step (pick_fused_block_e).
      interpret: run via the Pallas interpreter, the only path this kernel
        has (None resolves through ``backend.default_interpret``; a native
        backend raises NotImplementedError).
      gather_mode: "take" (vector lane gather) or "loop" (scalar-prefetch +
        dynamic-slice fallback).

    Returns:
      (rows, 128) lane-tiled padded y_G.
    """
    interpret = resolve_interpret(
        interpret, x2.dtype, g.dtype, w.dtype, d.dtype,
        kernel="poisson_assembled_fused",
    )
    if not interpret:
        raise NotImplementedError(NO_NATIVE_LOWERING)
    e, p = l2g.shape
    n1 = d.shape[0]
    if n1**3 != p:
        raise ValueError(f"p={p} is not (N+1)^3 for n1={n1}")
    if e % block_e:
        raise ValueError(
            f"E={e} not a multiple of block_e={block_e}; "
            "use ops.poisson_assembled_fused"
        )
    rows = x2.shape[0]
    grid = (e // block_e,)
    out_shape = jax.ShapeDtypeStruct((rows, LANES), x2.dtype)
    kf = derivative_factors(d)
    data_specs = [
        pl.BlockSpec((rows, LANES), lambda i: (0, 0)),  # x_G, resident
        pl.BlockSpec((block_e, 6, p), lambda i: (i, 0, 0)),
        pl.BlockSpec((block_e, p), lambda i: (i, 0)),
        pl.BlockSpec(kf.shape, lambda i: (0, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    out_spec = pl.BlockSpec((rows, LANES), lambda i: (0, 0))  # revisited acc
    scratch = [pltpu.VMEM((block_e, p), x2.dtype)] + body_scratch(
        block_e, p, x2.dtype
    )

    if gather_mode == "take":
        return pallas_call(
            functools.partial(_kernel_take, lam=lam),
            grid=grid,
            in_specs=[pl.BlockSpec((block_e, p), lambda i: (i, 0))] + data_specs,
            out_specs=out_spec,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name="poisson_assembled_fused",
        )(l2g, x2, g, w, kf, d.astype(kf.dtype))
    if gather_mode == "loop":
        # index maps receive the scalar-prefetch ref as a trailing argument
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((rows, LANES), lambda i, s: (0, 0)),
                pl.BlockSpec((block_e, 6, p), lambda i, s: (i, 0, 0)),
                pl.BlockSpec((block_e, p), lambda i, s: (i, 0)),
                pl.BlockSpec(kf.shape, lambda i, s: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((rows, LANES), lambda i, s: (0, 0)),
            scratch_shapes=scratch,
        )
        return pallas_call(
            functools.partial(_kernel_loop, lam=lam),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
            name="poisson_assembled_fused",
        )(l2g.reshape(-1), x2, g, w, kf, d.astype(kf.dtype))
    raise ValueError(f"unknown gather_mode {gather_mode!r}")
