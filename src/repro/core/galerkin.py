"""Materialized sparse Galerkin coarse operators for p-multigrid.

PR 3's ``pmg_coarse_op="galerkin"`` builds the variationally-exact coarse
operators ``A_{l+1} = R_l A_l P_l`` as *chained* matrix-free triple
products: every coarse A-apply recurses through the transfer chain all the
way to the fine grid, re-streaming the fine geometric factors on every
V-cycle visit to every coarse level.  That is exactly the redundant data
movement the hipBone paper eliminates for the fine operator by assembling
DOF storage — and coarse levels are *latency*-bound, so paying a fine-grid
sweep per coarse apply is the worst place to spend bandwidth.

This module materializes the triple product once at setup.  Because
p-coarsening keeps the element grid (only the polynomial degree drops) and
the prolongation ``P = Z_fᵀ W_f Ĵ Z_c`` is the exact nodal embedding of
the coarse SEM space into the fine one, the chained product collapses to
an element-block operator:

    PᵀAP = Z_cᵀ [ Ĵᵀ (S_L^e + λ W_e) Ĵ ] Z_c

— one dense (N_c+1)³ × (N_c+1)³ block per element, the standard FEM
sparsity (coarse DOFs couple only through shared elements).  The identity
behind the collapse: ``Ĵ Z_c x`` is a *continuous* element-local field
(adjacent elements interpolate identical shared-face values, because a
face value of the tensor-product interpolant depends only on that face's
coarse values), so the fine-level averaging gather-scatter inside the
chain is transparent to it,

    Z_f Z_fᵀ W_f (Ĵ Z_c) = Ĵ Z_c        (since Z_fᵀ W_f Z_f = I),

and both ``Z_f Z_fᵀ W_f`` factors of the expanded triple product cancel.
The identity is purely topological — it holds on deformed meshes and for
any SPD per-element operator, so deeper ladder rungs coarsen the *blocks*
directly (``B_{l+1} = Ĵᵀ B_l Ĵ``, :func:`coarsen_element_blocks`) without
ever touching the fine operator again.

Setup probes the fine element-local operator with the (N_c+1)³ lifted
coarse basis columns (``operator.local_operator_columns``) — a handful of
batched fine applies, once.  Apply time is scatter → one batched dense
element matvec → gather: **zero fine-operator applies per coarse apply**,
the same dataflow (and, sharded, the same single sum-exchange) as any
rediscretized level, but variationally exact.  The sharded path assembles
each rank's owned element blocks locally — ``w_local`` already carries the
global inverse degree, so no setup exchange is needed either
(``distributed.build_pmg_galerkin_blocks``).
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import sem
from .gather_scatter import Assembly
from .operator import local_operator_columns


# TPU's default f32 matmul is one bf16 pass; the solver needs full f32
_HI = jax.lax.Precision.HIGHEST

__all__ = [
    "tensor3_interp_matrix",
    "galerkin_element_blocks",
    "coarsen_element_blocks",
    "galerkin_ladder_blocks",
    "block_matvec_einsum",
    "galerkin_block_apply",
    "galerkin_assembled_diagonal",
]


def tensor3_interp_matrix(j: np.ndarray) -> np.ndarray:
    """The 3-D tensor-product lift Ĵ = J ⊗ J ⊗ J as a dense matrix.

    ``j``: (n_out+1, n_in+1) 1-D interpolation matrix.  Node order is
    (t, s, r) with r fastest, matching ``local_poisson`` /
    ``precond.tensor3_interp`` — the rightmost Kronecker factor acts on r.
    Setup-time numpy; the result is tiny (≤ a few k per side).
    """
    return np.kron(np.kron(j, j), j)


def _symmetrize(blocks: jax.Array) -> jax.Array:
    """Remove probing roundoff asymmetry so PCG symmetry holds exactly."""
    return 0.5 * (blocks + blocks.transpose(0, 2, 1))


def galerkin_element_blocks(
    g: jax.Array,
    d: jax.Array,
    lam: jax.Array | float,
    w: jax.Array | None,
    n_coarse: int,
) -> jax.Array:
    """Dense per-element Galerkin blocks ``Ĵᵀ (S_L^e + λW_e) Ĵ``.

    Batched probing of the chained triple product: the (N_c+1)³ columns of
    the lift Ĵ are pushed through the fine element-local operator
    (``local_operator_columns``) and contracted back with Ĵᵀ.  By the
    embedding identity in the module docstring the result assembles (via
    ``Z_cᵀ · Z_c``) to exactly ``PᵀAP`` on coarse DOFs.

    Args:
      g: (E, 6, p_f) fine geometric factors.
      d: (N_f+1, N_f+1) fine 1-D derivative matrix.
      lam: screen parameter λ.
      w: (E, p_f) fine inverse-degree weights (the hipBone λW screen) or
        None for the λI screen.
      n_coarse: coarse polynomial degree N_c < N_f.

    Returns:
      (E, p_c, p_c) symmetric blocks, p_c = (N_c+1)³, in ``g``'s dtype —
      assembled once in fp32 when the caller probes a cast problem (the
      mixed-precision path).
    """
    n_fine = d.shape[0] - 1
    jhat = jnp.asarray(
        tensor3_interp_matrix(sem.interpolation_matrix(n_coarse, n_fine)),
        g.dtype,
    )
    cols = local_operator_columns(g, d, lam, w, jhat)    # (E, p_f, p_c)
    return _symmetrize(jnp.einsum("pj,epk->ejk", jhat, cols, precision=_HI))


def coarsen_element_blocks(blocks: jax.Array, j: np.ndarray) -> jax.Array:
    """Next-rung blocks ``B_{l+1,e} = Ĵᵀ B_{l,e} Ĵ`` — no operator probes.

    ``j``: (n_f+1, n_c+1) 1-D interpolation between the two ladder levels
    (``sem.interpolation_matrix(n_c, n_f)``).  Two batched contractions of
    already-materialized blocks; the fine grid is never revisited.
    """
    jhat = jnp.asarray(tensor3_interp_matrix(j), blocks.dtype)
    return _symmetrize(jnp.einsum("pj,epq,qk->ejk", jhat, blocks, jhat, precision=_HI))


def galerkin_ladder_blocks(
    g: jax.Array,
    d: jax.Array,
    lam: jax.Array | float,
    w: jax.Array | None,
    degrees: Sequence[int],
) -> list[jax.Array]:
    """Materialized blocks for every coarse rung of a degree ladder.

    ``degrees[0]`` is the fine degree (of ``g``/``d``/``w``); the returned
    list holds one (E, p_c, p_c) block stack per coarse degree
    ``degrees[1:]``.  The fine operator is probed exactly once (for
    ``degrees[1]``); deeper rungs contract the previous rung's blocks.
    """
    degrees = tuple(int(n) for n in degrees)
    if len(degrees) < 2:
        raise ValueError(f"galerkin ladder needs >= 2 levels, got {degrees}")
    out = [galerkin_element_blocks(g, d, lam, w, degrees[1])]
    for nf, nc in zip(degrees[1:], degrees[2:]):
        out.append(
            coarsen_element_blocks(out[-1], sem.interpolation_matrix(nc, nf))
        )
    return out


def block_matvec_einsum(blocks: jax.Array, u: jax.Array) -> jax.Array:
    """Reference batched element matvec ``y_e = B_e u_e`` (XLA einsum).

    XLA lowers this to one batched MXU matmul; ``kernels.ops.block_matvec``
    is the explicit Pallas variant with the same contract.
    """
    return jnp.einsum("eij,ej->ei", blocks, u, precision=_HI)


def galerkin_block_apply(
    blocks: jax.Array,
    asm: Assembly,
    *,
    matvec: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
) -> Callable[[jax.Array], jax.Array]:
    """Assembled coarse-operator apply ``x → Z_cᵀ [B_e (Z_c x)_e]``.

    Single-device form: Z_c, one batched dense element matvec, Z_cᵀ — no
    fine-operator work; ``asm`` is the coarse level's
    ``gather_scatter.assembly``.  ``matvec`` lets callers swap in the Pallas
    batched matvec (``kernels.ops.block_matvec``); default is the einsum.
    The sharded analogue (the halo/interior split with this matvec as its
    element kernel, and the sum-exchange) is ``distributed._split_apply``.
    """
    mv = matvec or block_matvec_einsum
    return lambda x_c: asm.zt(mv(blocks, asm.z(x_c)))


def galerkin_assembled_diagonal(blocks: jax.Array, asm: Assembly) -> jax.Array:
    """Exact assembled diagonal of the materialized Galerkin operator.

    ``diag(Z_cᵀ B Z_c)`` = Z_cᵀ of the per-element block diagonals.  The
    pMG smoothers keep the *rediscretized* diagonal by default (the
    standard spectrally-equivalent choice, and what keeps ``galerkin_mat``
    iteration-identical to the chained form); this exact diagonal is
    exposed for experimentation and used by tests as an independent
    cross-check of the block assembly.
    """
    return asm.zt(jnp.diagonal(blocks, axis1=1, axis2=2))
