"""CPU tests of the sharded cell's driver (``drivers/dist_cg.py``) and readers.

The driver runs in a subprocess on 4 fake CPU devices, at a tiny size:
degree 3 on 2x2x2 elements per chip, grid (2, 2, 1).  The readers run
here on synthetic records.
"""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

TINY_X4 = {"name": "tiny_x4", "driver": "dist_cg", "chips": 4, "grid": [2, 2, 1],
           "elements_per_chip": [2, 2, 2], "degree": 3, "lam": 1.0, "dtype": "float32",
           "exchange": "face_sweep"}

# The tiny system converges well inside fixed100's 100 iterations, so
# which solves a timed window reaches would decide what it judges.  This
# driver cycles solve i through the right-hand sides of i mod 4, so that
# every window judges the same four solves whatever the machine's speed.
CYCLED = """
import dist_cg


class Driver(dist_cg.Driver):
    def solve(self, i):
        return super().solve(i % 4)

    def rhs(self, i):
        return super().rhs(i % 4)


def build(config, traffic, seed, devices):
    return Driver(config, traffic, seed, devices)
"""


def tiny_x4_bench(root):
    """A benchmark directory at ``root`` with one tiny four-chip cell."""
    for sub in ("drivers", "metrics", "traffic"):
        shutil.copytree(os.path.join(HERE, sub), root / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "configs").mkdir(exist_ok=True)
    (root / "cells").mkdir(exist_ok=True)
    shutil.copy(os.path.join(HERE, "peaks.json"), root)
    (root / "drivers" / "dist_cg_cycled.py").write_text(CYCLED)
    (root / "configs" / "tiny_x4.json").write_text(json.dumps(TINY_X4))
    (root / "configs" / "tiny_x4_cycled.json").write_text(
        json.dumps(dict(TINY_X4, name="tiny_x4_cycled", driver="dist_cg_cycled")))
    limits = run.Bench().limits("nekbone_n7_x4.fixed100")
    for cfg in ("tiny_x4", "tiny_x4_cycled"):
        (root / "cells" / f"{cfg}.fixed100.json").write_text(json.dumps({"limits": limits}))
    spec = {
        "workloads": [{"name": f"{cfg}.fixed100", "config": cfg, "traffic": "fixed100",
                       "chips": 4} for cfg in ("tiny_x4", "tiny_x4_cycled")],
        "end_to_end": [{"name": m, "unit": "u"} for m in ("fom_gflops", "setup_s")],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def run_on_four_devices(code: str, root) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE, os.path.join(CHECKOUT, "src"), env.get("PYTHONPATH", "")])
    prelude = f"""
import sys
sys.path[:0] = [{str(root / "drivers")!r}]
import numpy as np
import jax
import run
bench = run.Bench({str(root)!r}, {str(root)!r})
"""
    p = subprocess.run([sys.executable, "-c", prelude + code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr
    return p.stdout


def test_tiny_x4_run_is_correct(tmp_path):
    """A whole run of the tiny cell, judged on four fixed solves."""
    tiny_x4_bench(tmp_path)
    out = run_on_four_devices("""
res = run.run_cell(bench, "tiny_x4_cycled.fixed100", 12345678901, 0.3, False,
                   require_tpu=False, log=lambda *a, **k: None)
assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1, res
assert set(res["metrics"]) == {"fom_gflops", "setup_s"}
assert res["device"]["count"] == 4
print("RESULT", res["checks"])
""", tmp_path)
    assert "RESULT" in out


FAULTS = {
    "none": "out",
    # the solve hands back its input boxes as the solution
    "unchanged": "(b, *out[1:])",
    # one rank's solution box zeroed
    "rank_zeroed": "(out[0].at[1].set(0.0), *out[1:])",
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_rhs_boxes_and_the_reference_check(tmp_path, fault):
    """rhs(i) is what the boxes hold at their global indices; the sound
    driver passes run.check_solutions against reference.py; a planted
    fault in the solve's output fails it."""
    tiny_x4_bench(tmp_path)
    run_on_four_devices(f"""
config, traffic = bench.config("tiny_x4"), bench.traffic("fixed100")
limits = bench.limits("tiny_x4.fixed100")
seed = 2 ** 33 + 5
driver = bench.driver("dist_cg").build(config, traffic, seed, jax.devices())
idx = driver._idx
for i in range(3):
    b, boxes = driver.rhs(i), np.asarray(driver._boxes(i), np.float64)
    assert b.shape == (idx.max() + 1,) and np.array_equal(b[idx], boxes)
assert not np.array_equal(driver.rhs(0), driver.rhs(1))
solve = driver._solve


def planted(b):
    out = solve(b)
    return {FAULTS[fault]}


driver._solve = planted
outs = [driver.solve(i) for i in range(3)]
stats = [driver.stats(o) for o in outs]
checks = run.check_solutions(driver, outs, stats, seed, traffic, limits)
print(checks)
assert run.passes(checks) == ({fault!r} == "none"), checks
""", tmp_path)


# ------------------------------------------------------------ readers
def _reader(name):
    return run.Bench().metric(name)


def _rec(**kw):
    base = dict(config=TINY_X4, traffic={}, setup_s=1.0, window_s=2.0, stats=[],
                ok_status={0, 1}, peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                trace=None, probe_s={})
    base.update(kw)
    return types.SimpleNamespace(**base)


TRACE = {"window_s": 2.0, "busy_s": 1.6, "collective_s": 0.2,
         "collective_exposed_s": 0.05, "devices": 4}


@pytest.mark.parametrize("name, want", [
    ("collective_exposed_pct.x4", 100 * 0.05 / 1.6),
    ("collective_pct.x4", 100 * 0.2 / 1.6),
    ("device_idle_pct.x4", 20.0),
])
def test_trace_readers(name, want):
    read = _reader(name).read
    assert read(_rec(trace=TRACE)) == pytest.approx(want)
    assert read(_rec()) is None


def test_dist_operator_roofline_is_one_over_chips_of_the_single_chip_formula():
    dist, single = _reader("dist_operator_roofline.x4"), _reader("operator_roofline.fixed100")
    assert dist.PROBE == single.PROBE == "operator"
    config = dict(TINY_X4, degree=7, elements_per_chip=[16, 16, 16])
    rec = _rec(config=config, probe_s={"operator": 0.035})
    got = dist.read(rec)
    assert got == pytest.approx(single.read(rec) / 4)
    # Eq. 4 bytes of the 5,720,625-DOF box over four chips' HBM bandwidth
    assert got == pytest.approx(100 * 314_200_456 / (4 * 819e9) / 0.035)
    assert dist.read(_rec(config=config)) is None


def test_setup_dist_build_reader():
    import jax.numpy as jnp

    from repro import obs
    from repro.comms.topology import ProcessGrid
    from repro.core.distributed import build_dist_problem

    read = _reader("setup_dist_build_s.x4").read
    obs.reset()
    assert read(_rec()) is None
    build_dist_problem(3, ProcessGrid((2, 2, 1)), (1, 1, 1), dtype=jnp.float32)
    got = read(_rec())
    assert got is not None and 0 < got < 60
    assert got == pytest.approx(
        (obs.spans("setup.build_dist_problem")[-1].end_ns
         - obs.spans("setup.build_dist_problem")[-1].start_ns) * 1e-9)
