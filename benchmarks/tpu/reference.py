"""Plain reference of the NekBone screened Poisson operator, and its control.

Independent of the program under test: nothing here imports ``repro``.
The NekBone/hipBone problem (arXiv:2202.12477) on a regular box of
``ex x ey x ez`` elements of degree N on the unit cube, with the algebraic
screen lambda*I and no essential boundary conditions, is

    A = Kx (x) My (x) Mz  +  Mx (x) Ky (x) Mz  +  Mx (x) My (x) Kz  +  lambda I

where K and M are the assembled 1-D GLL stiffness and (diagonal) mass
matrices along each axis.  On a box of congruent affine elements the
element-by-element SEM sum collapses exactly to this tensor product, so the
reference needs no element loop, no gather and no scatter.

Global DOFs are numbered lexicographically on the ``(ex*N+1, ey*N+1,
ez*N+1)`` lattice with x fastest; a vector of length N_G reshapes to
``(gz, gy, gx)``.

``judge`` reads a solution in float64 on the host.  ``control_solve``
runs the same reference on the device as a plain CG in float32 with every
contraction at ``high`` (three bfloat16 passes), one step below the
``highest`` the configuration states: the control that has to fail.
"""
from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def gll(n: int) -> tuple[np.ndarray, np.ndarray]:
    """GLL nodes and weights on [-1, 1] for degree n (float64)."""
    from numpy.polynomial import legendre

    pn = legendre.Legendre.basis(n)
    x = np.concatenate([[-1.0], np.sort(pn.deriv().roots().real), [1.0]])
    w = 2.0 / (n * (n + 1) * pn(x) ** 2)
    return x, w


@functools.lru_cache(maxsize=None)
def lagrange_derivative(n: int) -> np.ndarray:
    """D[i, j] = l_j'(x_i) for the Lagrange basis on the GLL nodes."""
    x, _ = gll(n)
    diff = x[:, None] - x[None, :]
    np.fill_diagonal(diff, 1.0)
    bary = 1.0 / np.prod(diff, axis=1)          # barycentric weights
    d = (bary[None, :] / bary[:, None]) / diff
    np.fill_diagonal(d, 0.0)
    np.fill_diagonal(d, -d.sum(axis=1))          # rows of D annihilate 1
    return d


def axis_matrices(n: int, ne: int) -> tuple[np.ndarray, np.ndarray]:
    """Assembled 1-D stiffness (dense) and mass (diagonal) on [0, 1].

    ``ne`` elements of width h; element stiffness (2/h) D^T W D, mass
    (h/2) W, summed over shared end nodes.
    """
    x, w = gll(n)
    d = lagrange_derivative(n)
    h = 1.0 / ne
    k_e = (2.0 / h) * d.T @ (w[:, None] * d)
    m_e = (h / 2.0) * w
    size = ne * n + 1
    k = np.zeros((size, size))
    m = np.zeros(size)
    for e in range(ne):
        s = slice(e * n, e * n + n + 1)
        k[s, s] += k_e
        m[s] += m_e
    return k, m


class Reference:
    """The reference operator for one deployment's global box."""

    def __init__(self, n: int, elems: tuple[int, int, int], lam: float):
        self.n, self.elems, self.lam = n, tuple(elems), float(lam)
        (self.kx, self.mx), (self.ky, self.my), (self.kz, self.mz) = (
            axis_matrices(n, ne) for ne in self.elems
        )
        self.shape = tuple(ne * n + 1 for ne in self.elems[::-1])  # (gz, gy, gx)
        self.n_global = int(np.prod(self.shape))

    def apply(self, x: np.ndarray) -> np.ndarray:
        """A x in float64 on the host; x has length N_G."""
        v = np.asarray(x, np.float64).reshape(self.shape)
        mx, my, mz = self.mx, self.my[:, None], self.mz[:, None, None]
        y = self.lam * v
        y += (mz * my) * (v @ self.kx.T)
        y += (mz * mx) * np.matmul(self.ky, v)
        y += (my * mx) * (self.kz @ v.reshape(self.shape[0], -1)).reshape(self.shape)
        return y.reshape(-1)

    def judge(self, b: np.ndarray, x: np.ndarray, rnorm: float) -> dict:
        """The numbers compared for one solve, in float64, 2-norms.

        ``true_residual`` is ||b - A x|| / ||b|| of the returned x;
        ``residual_gap`` is | ||b - A x|| - rnorm | / ||b||, how far that
        lies from the residual ``rnorm`` the solver reports.
        """
        b = np.asarray(b, np.float64)
        true, bnorm = np.linalg.norm(b - self.apply(x)), np.linalg.norm(b)
        return {"true_residual": float(true / bnorm),
                "residual_gap": float(abs(true - rnorm) / bnorm)}

    # ------------------------------------------------------------ control
    def device_apply(self, precision: str):
        """A as a jnp function of a float32 (N_G,) vector.

        ``precision`` is one that ``_einsum`` takes.
        """
        import jax.numpy as jnp

        f32 = lambda a: jnp.asarray(a, jnp.float32)
        kx, ky, kz = f32(self.kx), f32(self.ky), f32(self.kz)
        mx, my, mz = f32(self.mx), f32(self.my)[:, None], f32(self.mz)[:, None, None]
        lam, shape = self.lam, self.shape
        es = _einsum(precision)

        def apply(x):
            v = x.reshape(shape)
            y = lam * v
            y = y + (mz * my) * es("ij,zyj->zyi", kx, v)
            y = y + (mz * mx) * es("ij,zjx->zix", ky, v)
            y = y + (my * mx) * es("ij,jyx->iyx", kz, v)
            return y.reshape(-1)

        return apply


def _split(a):
    """a = hi + lo + rest with hi, lo bfloat16 values held in float32."""
    import jax.numpy as jnp

    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _einsum(precision: str):
    """einsum at ``"highest"``, or at ``"high"``: three bfloat16 passes.

    ``"high"`` is spelled out as the products hi*hi + hi*lo + lo*hi of
    bfloat16 parts, each exact in float32, so that it means the same on
    every backend (on the CPU ``Precision.HIGH`` changes nothing).
    """
    import jax
    import jax.numpy as jnp

    full = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=full)
    if precision != "high":
        raise ValueError(f"precision must be 'highest' or 'high', got {precision!r}")

    def es(eq, a, b):
        (ah, al), (bh, bl) = _split(a), _split(b)
        return (jnp.einsum(eq, ah, bh, precision=full)
                + jnp.einsum(eq, ah, bl, precision=full)
                + jnp.einsum(eq, al, bh, precision=full))

    return es


def cg(apply, b, *, n_iter: int, tol: float | None, precision: str):
    """Plain CG from x0 = 0: fixed ``n_iter`` steps, or until ||r|| <= tol ||b||.

    Returns ``(x, iterations, r.r)`` with r.r from the recursion, as a
    solver reports it.  Dots run at ``precision`` too.
    """
    import jax
    import jax.numpy as jnp

    es = _einsum(precision)
    dot = lambda a, c: es("i,i->", a, c)
    # a fixed count keeps stepping once r.r has underflowed: hold x there
    safe_div = lambda a, c: jnp.where(c != 0, a / jnp.where(c != 0, c, 1), 0.0)
    rr0 = dot(b, b)
    target = 0.0 if tol is None else tol * tol * rr0

    def cond(c):
        return (c[4] < n_iter) & (c[3] > target)

    def body(c):
        x, r, p, rr, k = c
        ap = apply(p)
        alpha = safe_div(rr, dot(p, ap))
        x, r = x + alpha * p, r - alpha * ap
        rr_new = dot(r, r)
        p = r + safe_div(rr_new, rr) * p
        return x, r, p, rr_new, k + 1

    x, _, _, rr, k = jax.lax.while_loop(
        cond, body, (jnp.zeros_like(b), b, b, rr0, jnp.asarray(0))
    )
    return x, k, rr


def control_solve(ref: Reference, *, n_iter: int, tol: float | None,
                  precision: str = "high"):
    """Jitted b -> (x, iterations, r.r) of the reference CG at ``precision``.

    The default ``"high"`` is the control; ``"highest"`` gives the
    reference at the configuration's own precision.
    """
    import jax

    apply = ref.device_apply(precision)
    return jax.jit(lambda b: cg(apply, b, n_iter=n_iter, tol=tol, precision=precision))
