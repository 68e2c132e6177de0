"""Single-chip solves through the program's own entry points.

``repro.core.build_problem`` -> ``poisson_assembled`` (the policy's
operator) -> optional ``repro.core.precond.make_preconditioner`` ->
``cg_assembled``, compiled once as one program per solve.  The
preconditioner is built at set-up and reused by every solve, as a pressure
solve reuses it across time steps.
"""
from __future__ import annotations

import numpy as np

import rhs


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp

        from repro.core import build_problem, cg_assembled, poisson_assembled
        from repro.core.precond import make_preconditioner

        self.degree = config["degree"]
        self.global_elems = tuple(config["elements_per_chip"])
        self.lam = config["lam"]
        prob = build_problem(self.degree, self.global_elems, lam=self.lam,
                             dtype=jnp.dtype(config["dtype"]))
        self.operator = poisson_assembled(prob)
        self.precond = None
        if traffic["precond"] != "none":
            self.precond, _ = make_preconditioner(
                traffic["precond"], prob, self.operator, **traffic["precond_kwargs"])
        n_iter, tol, pc, op = traffic["n_iter"], traffic["tol"], self.precond, self.operator
        self._solve = jax.jit(lambda b: cg_assembled(op, b, n_iter=n_iter, tol=tol, precond=pc))
        self._rhs = jax.jit(rhs.normal_fn(prob.n_global))
        self._kd = jnp.asarray(rhs.key_data(seed))
        self.probe_input = self._rhs(self._kd, 0)

    def reseed(self, seed: int):
        """Draw the right-hand sides of another seed (calibrate.py)."""
        import jax.numpy as jnp

        self._kd = jnp.asarray(rhs.key_data(seed))

    def solve(self, i: int):
        return self._solve(self._rhs(self._kd, i))

    def block(self, out):
        out.x.block_until_ready()

    def stats(self, out) -> tuple[int, int, float]:
        """(iterations, status, ||r|| as the solver reports it)."""
        return int(out.iterations), int(out.status), float(out.rdotr) ** 0.5

    def answer(self, out) -> np.ndarray:
        return np.asarray(out.x, np.float64)

    def rhs(self, i: int) -> np.ndarray:
        return np.asarray(self._rhs(self._kd, i), np.float64)


def build(config, traffic, seed, devices):
    return Driver(config, traffic, seed, devices)
