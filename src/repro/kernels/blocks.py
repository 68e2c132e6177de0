"""Pallas TPU kernel for the batched dense element-block matvec.

The materialized Galerkin coarse operator (``core.galerkin``) applies one
dense (p_c, p_c) block per element: ``y_e = B_e u_e``.  XLA's batched
einsum already lowers this to MXU matmuls; this kernel is the explicit
streaming form for the non-interpret TPU path, matching the repo's other
kernels: grid over *blocks of elements*, each step DMAs a
``(block_e, p, p)`` tile of stencil blocks plus its ``(block_e, p)`` input
tile HBM→VMEM, performs one element-batched ``dot_general`` (the element
batch rides the dot's batch dimension, so the MXU sees p×p matmuls back to
back), and writes the single output tile.  Coarse levels are
latency-bound, so the single-pass traffic bound — every block byte read
exactly once per apply — is the point.

The VMEM knob is ``block_e``; blocks dominate the footprint at
``block_e · p² · word`` bytes, so deep-ladder levels (p ≤ 125) batch many
elements per step while the widest coarse level (p = 729 on the N=15
ladder) streams element by element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import pallas_call, resolve_interpret

__all__ = ["block_matvec_pallas", "pick_block_matvec_e"]


def _kernel(b_ref, u_ref, out_ref):
    """One grid step: y_e = B_e u_e for block_e elements resident in VMEM."""
    b = b_ref[...]          # (Eb, p, p)
    u = u_ref[...]          # (Eb, p)
    acc = jnp.promote_types(u.dtype, jnp.float32)
    # element-batched matvec: batch dim 0, contract B's j with u's j
    y = jax.lax.dot_general(
        b.astype(acc), u.astype(acc),
        (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,  # TPU's default is one bf16 pass
        preferred_element_type=acc,
    )
    out_ref[...] = y.astype(out_ref.dtype)


def pick_block_matvec_e(
    p: int, dtype=jnp.float32, budget_bytes: int = 4 * 2**20
) -> int:
    """Largest power-of-two element batch whose tiles fit the VMEM budget.

    The 4 MB default leaves room for Mosaic's double-buffered pipelining,
    like ``kernels.poisson.pick_block_e``; the block tile (p² words/elt)
    dominates u/y (p words each).
    """
    word = jnp.dtype(dtype).itemsize
    eb = 256
    while eb > 1 and eb * (p * p + 2 * p) * word > budget_bytes:
        eb //= 2
    return eb


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def block_matvec_pallas(
    blocks: jax.Array,
    u: jax.Array,
    *,
    block_e: int,
    interpret: bool | None = None,
) -> jax.Array:
    """y[e] = blocks[e] @ u[e].  Shapes: (E, p, p), (E, p) -> (E, p).

    ``E`` must be a multiple of ``block_e`` (callers pad, see
    ``kernels.ops.block_matvec``).  ``interpret`` None resolves through
    ``backend.default_interpret``.
    """
    interpret = resolve_interpret(
        interpret, blocks.dtype, u.dtype, kernel="block_matvec"
    )
    e, p, _ = blocks.shape
    if e % block_e:
        raise ValueError(f"E={e} not a multiple of block_e={block_e}")
    grid = (e // block_e,)
    return pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, p, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_e, p), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_e, p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e, p), u.dtype),
        interpret=interpret,
        name="block_matvec",
    )(blocks, u)
