"""repro.obs: scope names in the compiled solve, host spans, compile counter.

The device scopes are checked on the optimized HLO that XLA's CPU backend
compiles (``op_name`` metadata keeps the JAX name stack there as it does
on a TPU, where a profiler trace reports it as ``tf_op``).
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro import obs
from repro.core import build_problem, cg_assembled, poisson_assembled
from repro.core.precond import make_preconditioner
from repro.serving.engine import SolveRequest, SolverEngine

SCOPED = re.compile(r'op_name="([^"]*)"')
# instructions that compute nothing a layer owns: tuple plumbing, layout
# and loop-carry copies XLA inserts, literals and fills made from literals
# alone (XLA shares one zero fill between scopes), and the integer and
# pred scalars of the loop's own control (counter, trip test)
TRIVIAL = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast", "copy")
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather", "all-to-all",
               "reduce-scatter")


def computations(hlo: str) -> dict[str, list[str]]:
    """Instruction lines of each computation of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name and re.match(r"^\s+(ROOT )?%", line):
            comps[name].append(line.strip())
    return comps


def instruction(line: str) -> tuple[str, str, str]:
    """(result shape, opcode, op_name or '') of one instruction line."""
    m = re.match(r"(?:ROOT )?%[\w.\-]+ = (.+?) ([\w\-]+)\(", line)
    shape, opcode = (m.group(1), m.group(2)) if m else ("", "")
    name = SCOPED.search(line)
    return shape, opcode, name.group(1) if name else ""


def is_trivial(line: str) -> bool:
    shape, opcode, _ = instruction(line)
    operands = re.match(r"[^(]*\(([^)]*)\)", line.split(" = ", 1)[-1])
    literal = operands and all(o.strip().startswith("%constant")
                               for o in operands.group(1).split(","))
    return (opcode in TRIVIAL or bool(literal)
            or re.fullmatch(r"([su](32|64)|pred)\[\]", shape) is not None)


def loop_bodies(hlo: str) -> list[list[str]]:
    comps = computations(hlo)
    names = set(re.findall(r"\bbody=%([\w.\-]+)", hlo))
    names |= set(re.findall(r"\bcondition=%([\w.\-]+)", hlo))
    return [comps[n] for n in sorted(names)]


def program_scopes(hlo: str) -> set[str]:
    return {p[len(obs.PREFIX):] for n in SCOPED.findall(hlo) for p in n.split("/")
            if p.startswith(obs.PREFIX)}


# ------------------------------------------------------------ compiled solves
def _single_device_hlo(kind: str) -> str:
    prob = build_problem(3, (2, 2, 2), dtype=jnp.float32)
    op = poisson_assembled(prob)
    b = jnp.ones(prob.n_global, jnp.float32)
    if kind == "fixed":
        solve = lambda b: cg_assembled(op, b, n_iter=5)
    else:
        pc, _ = make_preconditioner("pmg", prob, op, pmg_coarse_solve="chebyshev",
                                    pmg_coarse_iters=4)
        solve = lambda b: cg_assembled(op, b, n_iter=50, tol=1e-6, precond=pc)
    return jax.jit(solve).lower(b).compile().as_text()


_DIST = """
import json, os
os.environ["HIPBONE_EXCHANGE_CACHE"] = ""
import jax, jax.numpy as jnp, numpy as np
from repro.compat import make_mesh
from repro.comms.topology import ProcessGrid
from repro.core.distributed import build_dist_problem, dist_cg

grid = ProcessGrid((2, 2, 1))
mesh = make_mesh((4,), ("ranks",))
prob = build_dist_problem(3, grid, (1, 1, 2), lam=0.8, dtype=jnp.float32)
b = jnp.asarray(np.random.default_rng(0).standard_normal((grid.size, prob.m3)),
                jnp.float32)
run = dist_cg(prob, mesh, b, n_iter=30, tol=1e-6, precond="pmg",
              exchange="face_sweep")
print(json.dumps(jax.jit(run).lower().compile().as_text()))
"""

_HLO: dict[str, str] = {}


def hlo_of(kind: str) -> str:
    if kind not in _HLO:
        if kind == "dist_pmg":
            _HLO[kind] = json.loads(run_subprocess(_DIST, devices=4).splitlines()[-1])
        else:
            _HLO[kind] = _single_device_hlo(kind)
    return _HLO[kind]


OPERATOR = {"op.scatter", "op.local", "op.gather"}
KRYLOV = {"cg.init", "cg.iteration", "cg.operator"}
PMG = {"cg.precond", "pmg.l0", "pmg.l1", "pmg.coarse"}


@pytest.mark.parametrize("kind, expected", [
    ("fixed", KRYLOV | OPERATOR),
    ("pmg", KRYLOV | OPERATOR | PMG),
    ("dist_pmg", KRYLOV | PMG | {"cg.allreduce", "halo.sum", "halo.copy",
                                 "halo.site.rhs", "halo.site.operator",
                                 "halo.site.restrict", "halo.site.prolong"}),
])
def test_compiled_solve_carries_its_scopes(kind, expected):
    found = program_scopes(hlo_of(kind))
    assert expected <= found, sorted(expected - found)
    declared = re.compile("|".join(re.escape(n).replace(re.escape("{i}"), r"\d+")
                                   for n in obs.SCOPES))
    assert all(declared.fullmatch(s) for s in found), sorted(found)


@pytest.mark.parametrize("kind", ["fixed", "pmg", "dist_pmg"])
def test_every_op_of_the_solve_loop_has_a_scope(kind):
    bodies = loop_bodies(hlo_of(kind))
    assert bodies
    bare = []
    for body in bodies:
        for line in body:
            if not is_trivial(line) and obs.PREFIX not in instruction(line)[2]:
                bare.append(line[:200])
    assert not bare, "\n".join(bare)


def test_every_collective_of_dist_cg_sits_under_an_exchange_scope():
    hlo = hlo_of("dist_pmg")
    seen = 0
    for body in computations(hlo).values():
        for line in body:
            _, opcode, name = instruction(line)
            if opcode.startswith(COLLECTIVES):
                seen += 1
                assert "/hb.halo." in "/" + name or "/hb.cg.allreduce" in "/" + name, line
    assert seen


# ------------------------------------------------------------ host spans
def test_undeclared_names_are_refused():
    with pytest.raises(ValueError):
        obs.scope("op.mystery")
    with pytest.raises(ValueError):
        obs.span("setup.mystery")
    obs.scope("pmg.l7")          # a family takes any level
    obs.span("setup.precond.l3")


def test_span_nests_and_records_its_parent():
    obs.reset()
    with obs.span("engine.dispatch"):
        with obs.span("engine.solve") as inner:
            pass
        with obs.span("engine.block"):
            pass
    got = obs.spans()
    assert [(s.name, s.parent) for s in got] == [
        ("engine.solve", "engine.dispatch"), ("engine.block", "engine.dispatch"),
        ("engine.dispatch", None)]
    outer = got[-1]
    assert all(outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns for s in got[:2])
    assert inner.name == "engine.solve"
    assert obs.spans("engine.block") == [got[1]]


def test_setup_spans_of_the_problem_and_the_pmg_levels():
    obs.reset()
    prob = build_problem(7, (2, 2, 2), dtype=jnp.float32)
    make_preconditioner("pmg", prob, poisson_assembled(prob))
    build = obs.spans("setup.build_problem")
    assert len(build) == 1 and build[0].parent is None
    for child in ("setup.mesh", "setup.geometry", "setup.upload"):
        assert any(s.parent == "setup.build_problem" for s in obs.spans(child)), child
    (pre,) = obs.spans("setup.precond")
    levels = {s.name for s in obs.spans() if s.parent == "setup.precond"}
    assert levels == {f"setup.precond.l{i}" for i in range(4)}  # N = 7, 4, 2, 1
    assert pre.end_ns - pre.start_ns > 0


def test_compile_counter_counts_a_fresh_jit_not_a_cached_call():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.25)
    x = jnp.arange(7.0)
    with obs.span("engine.solve") as first:
        f(x).block_until_ready()
    with obs.span("engine.solve") as again:
        f(x).block_until_ready()
    assert first.compiles == 1 and first.compile_s > 0
    assert again.compiles == 0 and again.compile_s == 0.0
    assert obs.counters()["engine.solve"]["compiles"] >= 1


def test_engine_records_carry_their_compiles():
    prob = build_problem(2, (2, 2, 2), dtype=jnp.float32)
    rng = np.random.default_rng(3)
    reqs = [SolveRequest(prob, jnp.asarray(rng.standard_normal(prob.n_global),
                                           jnp.float32), kind="jacobi", tol=1e-5)
            for _ in range(2)]
    engine = SolverEngine()
    engine.solve(reqs)
    (rec,) = engine.records
    assert rec["compiles"] >= 1 and rec["compile_s"] > 0
    assert rec["compile_s"] <= rec["solve_s"] + rec["setup_build_s"] + 1.0
