"""Sharded solves over a chip mesh through the program's own entry points.

``repro.core.distributed.build_dist_problem`` on the configuration's
process grid -> ``dist_solver`` (the ``dist_cg`` solve, compiled once with
the right-hand side as its argument) over a 1-D mesh of the cell's
chips.  Each rank holds its padded, consistent box of the global lattice.

The right-hand side of solve i is drawn by ``rhs.normal_fn`` over the
whole global lattice from (seed, i), as on one chip, and taken into the
rank boxes by ``box_global_indices`` in the same program, so that replicas
agree by construction and ``rhs(i)`` is the very vector the boxes hold.
``answer`` puts the solution boxes back on the global lattice for the
reference.

``operator`` is ``solve.operator``: the A-apply the solve iterates with,
on its own.  It is handed the solve's own sharded arrays through
``probe_input`` (the first right-hand side's boxes and
``solve.operator_operands``) rather than closed over, so that the probe's
program holds no copy of them as constants.  The problem is uploaded laid
out over the mesh, so each chip holds its own rank's arrays and no more.
"""
from __future__ import annotations

import numpy as np

import rhs


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.comms.topology import ProcessGrid
        from repro.compat import make_mesh
        from repro.core.distributed import (
            box_global_indices, build_dist_problem, dist_solver,
        )

        if traffic["precond"] != "none":
            raise ValueError(f"the dist_cg driver runs no preconditioner, "
                             f"traffic asks for {traffic['precond']!r}")
        grid = ProcessGrid(tuple(config["grid"]))
        per_chip = tuple(config["elements_per_chip"])
        self.degree = config["degree"]
        self.global_elems = tuple(g * e for g, e in zip(grid.shape, per_chip))
        self.lam = config["lam"]
        mesh = make_mesh((grid.size,), ("ranks",), devices=list(devices)[: grid.size])
        prob = build_dist_problem(self.degree, grid, per_chip, lam=self.lam,
                                  dtype=jnp.dtype(config["dtype"]), mesh=mesh)
        self._solve = dist_solver(prob, mesh, n_iter=traffic["n_iter"], tol=traffic["tol"],
                                  exchange=config["exchange"])
        self.operator = lambda a: self._solve.operator(a[0], *a[1])

        self._n_global = prob.n_global
        self._idx = box_global_indices(prob)
        boxes = NamedSharding(mesh, P("ranks"))
        whole = NamedSharding(mesh, P())
        normal = rhs.normal_fn(prob.n_global)
        # (global b, its rank boxes) in one program; the index is an operand
        self._draw = jax.jit(lambda kd, i, idx: (lambda b: (b, b[idx]))(normal(kd, i)),
                             out_shardings=(whole, boxes))
        self._idx_dev = jax.device_put(np.asarray(self._idx, np.int32), boxes)
        self._kd = jnp.asarray(rhs.key_data(seed))
        self.probe_input = (self._boxes(0), self._solve.operator_operands)

    def reseed(self, seed: int):
        """Draw the right-hand sides of another seed (calibrate.py)."""
        import jax.numpy as jnp

        self._kd = jnp.asarray(rhs.key_data(seed))

    def _boxes(self, i: int):
        return self._draw(self._kd, i, self._idx_dev)[1]

    def solve(self, i: int):
        return self._solve(self._boxes(i))

    def block(self, out):
        out[0].block_until_ready()

    def stats(self, out) -> tuple[int, int, float]:
        """(iterations, status, ||r|| as the solver reports it)."""
        _, rdotr, iterations, status, _ = out
        return int(iterations), int(status), float(rdotr) ** 0.5

    def answer(self, out) -> np.ndarray:
        """The solution boxes on the global lattice, float64."""
        boxes = np.asarray(out[0], np.float64)
        x = np.empty(self._n_global)
        x[self._idx.reshape(-1)] = boxes.reshape(-1)
        return x

    def rhs(self, i: int) -> np.ndarray:
        return np.asarray(self._draw(self._kd, i, self._idx_dev)[0], np.float64)


def build(config, traffic, seed, devices):
    return Driver(config, traffic, seed, devices)
