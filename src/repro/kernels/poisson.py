"""Pallas TPU kernel for the fused hipBone operator  y_L = (S_L + λW) x_L.

TPU adaptation of the paper's CUDA/HIP operator kernel (DESIGN.md §3):

* GPU version: one threadblock per element (3-D block for N<9, 2-D
  layer-by-layer for N>=9), shared memory as scratchpad, multiple elements
  per block to avoid masked lanes.
* TPU version: grid over *blocks of elements*; each grid step streams a
  (block_e, p) tile of DOFs plus its (block_e, 6, p) geometric factors and
  (block_e, p) weights HBM->VMEM and writes the single output tile. The
  kernel is a single pass over all seven input streams — the paper's
  "perfect caching" traffic bound  word*N_G + (4 + 8*word)*N_L  is met by
  construction, because nothing is re-read.
* The element's p = (N+1)^3 nodes (order t, s, r; r fastest) stay on the
  lane axis: Mosaic cannot split the lane axis into (t, s, r), so the
  tensor-product derivatives are applied as 2-D matmuls against Kronecker
  factors of the 1-D derivative matrix D (``derivative_factors``):
    - p <= 512 (N <= 7): the whole element is one row; the gradient is
      u @ [Dr^T | Ds^T | Dt^T] with Dr = I⊗I⊗D, Ds = I⊗D⊗I, Dt = D⊗I⊗I,
      and the divergence contracts with the same factors transposed;
    - larger p (N = 15): the row splits into n1 lane-aligned chunks of
      n1^2 nodes (one t-plane each); r and s derivatives are matmuls of
      each chunk with the n1^2-wide factors I⊗D and D⊗I, and the t
      derivative is a D-weighted sum of chunks on the VPU (D read as SMEM
      scalars).  A p x p factor at N=15 would take 64 MB.
  The Kronecker form spends more MXU FLOPs than the (N+1)-wide
  contractions; it is what lowers without lane reshapes.
* The GPU occupancy knob (registers/warp) becomes the VMEM-footprint knob
  ``block_e``, swept in benchmarks/table1_blocks.py.

The scatter Z (read of x_G) happens outside at the XLA level — TPU has
no efficient per-lane random HBM gather inside a kernel. On a box mesh Z
is dense lattice slices (``core.gather_scatter.assembly``), else XLA's
``take``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import pallas_call, resolve_interpret

__all__ = [
    "poisson_local_pallas",
    "local_body",
    "body_scratch",
    "derivative_factors",
    "vmem_bytes_per_block",
    "pick_block_e",
    "KERNEL_VMEM_BUDGET",
    "KERNEL_VMEM_LIMIT",
]

# whole-element rows up to this many nodes (N <= 7); chunked rows above
FULL_ROW_MAX_P = 512
# block_e is sized to this working set; Mosaic's scoped VMEM limit is
# raised to KERNEL_VMEM_LIMIT (v5e has 128 MiB of VMEM per core)
KERNEL_VMEM_BUDGET = 24 * 2**20
KERNEL_VMEM_LIMIT = 64 * 2**20

_HIGHEST = jax.lax.Precision.HIGHEST


def _chunk(n1: int) -> int:
    """Lane width of one row chunk: p for whole-element rows, else n1^2."""
    p = n1**3
    return p if p <= FULL_ROW_MAX_P else n1 * n1


def derivative_factors(d: jax.Array, dtype=None) -> jax.Array:
    """Kronecker gradient factors, (c, k·c) for chunk width c.

    Whole-element rows (p <= 512): ``[Dr^T | Ds^T | Dt^T]`` with the three
    p x p operators Dr = I⊗I⊗D, Ds = I⊗D⊗I, Dt = D⊗I⊗I.  Chunked rows:
    ``[(I⊗D)^T | (D⊗I)^T]`` over one t-plane of n1^2 nodes.  Entries are
    products with 0/1, so the factors are exact copies of D's entries.
    """
    n1 = d.shape[0]
    dd = d.astype(dtype or jnp.promote_types(d.dtype, jnp.float32))
    eye = jnp.eye(n1, dtype=dd.dtype)
    if _chunk(n1) == n1**3:
        ops_ = [
            jnp.kron(jnp.kron(eye, eye), dd),
            jnp.kron(jnp.kron(eye, dd), eye),
            jnp.kron(jnp.kron(dd, eye), eye),
        ]
    else:
        ops_ = [jnp.kron(eye, dd), jnp.kron(dd, eye)]
    return jnp.concatenate([o.T for o in ops_], axis=1)


def _mm(a, b, acc):
    """a @ b at full precision (TPU's default f32 matmul is one bf16 pass)."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=acc,
    )


def _mm_t(a, b, acc):
    """a @ b^T at full precision."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=acc,
    )


def _metric(g, ur, us, ut):
    """(wr, ws, wt) = G (ur, us, ut) for the packed symmetric 3x3 metric."""
    wr = g[0] * ur + g[1] * us + g[2] * ut
    ws = g[1] * ur + g[3] * us + g[4] * ut
    wt = g[2] * ur + g[4] * us + g[5] * ut
    return wr, ws, wt


def local_body(u_ref, g_ref, w_ref, kf_ref, d_ref, y_ref, ut_ref, wt_ref, *, lam):
    """y_ref <- (S_L + λW) u for one element block resident in VMEM.

    Shared between the element-local kernel below and the single-pass fused
    assembled kernel (kernels/poisson_fused.py).  Operands are refs so the
    chunked form streams one lane-aligned t-plane at a time instead of
    keeping every plane live (which spills and slows Mosaic's compile).

    Args:
      u_ref: (Eb, p) element DOFs.
      g_ref: (Eb, 6, p) packed geometric factors.
      w_ref: (Eb, p) screen weights.
      kf_ref: ``derivative_factors``.
      d_ref: (n1, n1) derivative matrix in SMEM (chunked rows only).
      y_ref, ut_ref, wt_ref: (Eb, p) scratch in the accumulation dtype
        (``promote_types(u.dtype, f32)`` — fp64 inputs accumulate in
        fp64); y_ref receives the result, ut/wt_ref serve chunked rows.
    """
    eb, p = u_ref.shape
    acc = y_ref.dtype
    c = kf_ref.shape[0]
    if c == p:
        u = u_ref[...].astype(acc)
        grads = [_mm(u, kf_ref[:, j * p:(j + 1) * p], acc) for j in range(3)]
        g = [g_ref[:, k, :].astype(acc) for k in range(6)]
        fluxes = _metric(g, *grads)
        out = lam * (w_ref[...].astype(acc) * u)
        for j in range(3):
            out = out + _mm_t(fluxes[j], kf_ref[:, j * p:(j + 1) * p], acc)
        y_ref[...] = out
        return

    n1 = p // c
    kr, ks = kf_ref[:, 0:c], kf_ref[:, c:2 * c]
    planes = [slice(t * c, (t + 1) * c) for t in range(n1)]
    chunk = lambda ref, t: ref[:, planes[t]].astype(acc)
    # t-derivative: ut_k = sum_c D[k, c] u_c across lane-aligned t-planes
    for k in range(n1):
        ut_k = d_ref[k, 0].astype(acc) * chunk(u_ref, 0)
        for cc in range(1, n1):
            ut_k = ut_k + d_ref[k, cc].astype(acc) * chunk(u_ref, cc)
        ut_ref[:, planes[k]] = ut_k
    for t in range(n1):
        u_t = chunk(u_ref, t)
        g = [g_ref[:, k, planes[t]].astype(acc) for k in range(6)]
        wr, ws, wt = _metric(
            g, _mm(u_t, kr, acc), _mm(u_t, ks, acc), ut_ref[:, planes[t]]
        )
        wt_ref[:, planes[t]] = wt
        y_ref[:, planes[t]] = (
            _mm_t(wr, kr, acc) + _mm_t(ws, ks, acc)
            + lam * (chunk(w_ref, t) * u_t)
        )
    # t-divergence: out_t += sum_k D[k, t] wt_k
    for t in range(n1):
        out_t = y_ref[:, planes[t]]
        for k in range(n1):
            out_t = out_t + d_ref[k, t].astype(acc) * wt_ref[:, planes[k]]
        y_ref[:, planes[t]] = out_t


def body_scratch(block_e: int, p: int, dtype) -> list:
    """The three (block_e, p) accumulation-dtype scratch refs of local_body."""
    acc = jnp.promote_types(jnp.dtype(dtype), jnp.float32)
    return [pltpu.VMEM((block_e, p), acc) for _ in range(3)]


def _kernel(u_ref, g_ref, w_ref, kf_ref, d_ref, out_ref, *scratch, lam: float):
    """One grid step: apply (S_L + λW) to block_e elements resident in VMEM."""
    local_body(u_ref, g_ref, w_ref, kf_ref, d_ref, *scratch, lam=lam)
    out_ref[...] = scratch[0][...].astype(out_ref.dtype)


def vmem_bytes_per_block(block_e: int, n1: int, dtype=jnp.float32) -> int:
    """Estimated VMEM working set of one grid step.

    Double-buffered input/output tiles (u, w, out and the 6-plane G block,
    whose 6 sublanes pad to 8), the resident derivative factors (also
    double-buffered), the three scratch planes of ``local_body`` and the
    f32 temporaries (u, the three gradients, the three fluxes, out).
    """
    p = n1**3
    word = jnp.dtype(dtype).itemsize
    acc = jnp.promote_types(jnp.dtype(dtype), jnp.float32).itemsize
    c = _chunk(n1)
    io = 2 * block_e * p * (3 + 8) * word
    factors = 2 * c * (3 * c if c == p else 2 * c) * acc
    tmp = block_e * p * (3 + 8) * acc
    return io + factors + tmp


def pick_block_e(
    n_degree: int, dtype=jnp.float32, budget_bytes: int = KERNEL_VMEM_BUDGET
) -> int:
    """Largest power-of-two element block whose working set fits the budget.

    The budget leaves VMEM room for double-buffered pipelining (Mosaic
    overlaps the next tile's HBM->VMEM DMA with current compute, the TPU
    analogue of the paper's >1 waves/CU occupancy goal).
    """
    n1 = n_degree + 1
    eb = 256
    while eb > 1 and vmem_bytes_per_block(eb, n1, dtype) > budget_bytes:
        eb //= 2
    return eb


@functools.partial(
    jax.jit,
    static_argnames=("lam", "block_e", "interpret"),
)
def poisson_local_pallas(
    u: jax.Array,
    g: jax.Array,
    w: jax.Array,
    d: jax.Array,
    *,
    lam: float,
    block_e: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused (S_L + λW) u for element-blocked tiles.

    Args:
      u: (E, p) local DOFs, p=(N+1)^3. E must be a multiple of block_e
         (ops.poisson_local pads).
      g: (E, 6, p) packed geometric factors.
      w: (E, p) inverse-degree weights (pass ones for the plain S_L + λI).
      d: (n1, n1) derivative matrix.
      lam: screen parameter (static).
      block_e: elements per grid step; default via pick_block_e.
      interpret: run the kernel body in interpret mode (CPU validation);
        None resolves through ``backend.default_interpret``.

    Returns:
      (E, p) y_L.
    """
    interpret = resolve_interpret(
        interpret, u.dtype, g.dtype, w.dtype, d.dtype, kernel="poisson_local"
    )
    e, p = u.shape
    n1 = d.shape[0]
    if n1**3 != p:
        raise ValueError(f"p={p} is not (N+1)^3 for n1={n1}")
    eb = block_e or pick_block_e(n1 - 1, u.dtype)
    eb = min(eb, e)
    if e % eb:
        raise ValueError(f"E={e} not a multiple of block_e={eb}; use ops.poisson_local")
    kf = derivative_factors(d)

    return pallas_call(
        functools.partial(_kernel, lam=lam),
        grid=(e // eb,),
        in_specs=[
            pl.BlockSpec((eb, p), lambda i: (i, 0)),
            pl.BlockSpec((eb, 6, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((eb, p), lambda i: (i, 0)),
            pl.BlockSpec(kf.shape, lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((eb, p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e, p), u.dtype),
        scratch_shapes=body_scratch(eb, p, u.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=KERNEL_VMEM_LIMIT),
        name="poisson_local",
    )(u, g, w, kf, d.astype(kf.dtype))
