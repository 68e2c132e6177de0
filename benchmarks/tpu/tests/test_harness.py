"""CPU tests of the on-chip benchmark harness.

    PYTHONPATH=src python -m pytest -q benchmarks/tpu/tests

No TPU is needed and none is touched: JAX is imported inside the tests
only, on the CPU.  The cells here are tiny copies of the real ones, run
through ``run.run_cell`` with the platform check skipped.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
import tracefile  # noqa: E402
import work  # noqa: E402

TESTDATA = os.path.join(HERE, "testdata")


# ------------------------------------------------------------ work and peaks
# the cells' global boxes: nekbone_n7, nekbone_n15, and nekbone_n7 per chip
# on a (2,2,1) grid of four chips (Open questions in PERF.md)
@pytest.mark.parametrize("n, elems, flops_eq3, flops_eq4, bytes_eq4", [
    (7, (16, 16, 16), 272_629_760, 239_075_328, 78_652_040),
    (15, (8, 8, 8), 473_956_352, 440_401_920, 81_281_352),
    (7, (32, 32, 16), 1_090_519_040, 956_301_312, 314_200_456),
])
def test_work_counts_of_the_cells(n, elems, flops_eq3, flops_eq4, bytes_eq4):
    e = int(np.prod(elems))
    assert work.nekbone_flops_per_iter(e, n) == flops_eq3
    assert work.operator_flops(e, n) == flops_eq4
    assert work.operator_bytes(e, n, work.n_global(elems, n), word=4) == bytes_eq4


def test_every_cell_has_its_files():
    b = run.Bench()
    for w in b.spec["workloads"]:
        config = b.config(w["config"])
        assert config["chips"] == w["chips"] == int(np.prod(config["grid"]))
        b.traffic(w["traffic"])
        b.driver(config["driver"])
        limits = b.limits(w["name"])
        assert set(limits) == set(run.CHECKS) and min(limits.values()) > 0
        for trace in (False, True):
            for m in b.metrics_of(w["name"], trace):
                assert hasattr(b.metric(m["name"]), "read")


def test_peaks_lookup_by_device_kind():
    peaks = run.Bench().peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.Bench().peaks("TPU v9 imaginary")


# ------------------------------------------------------------ refusal
def _result_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "nekbone_n7.fixed100", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "tpu",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/tpu/run.py", "--workload",
         "nekbone_n7.fixed100", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)


# ------------------------------------------------------------ tiny cells on the CPU
TINY = {"degree": 3, "elements_per_chip": [3, 2, 2], "lam": 1.0, "dtype": "float32"}


def tiny_bench(root, *, config="tiny", traffic="fixed100", metrics=("fom_gflops", "setup_s")):
    """A benchmark directory at ``root`` with one tiny cell, found by name."""
    for sub in ("drivers", "metrics", "traffic"):
        shutil.copytree(os.path.join(HERE, sub), root / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "configs").mkdir(exist_ok=True)
    (root / "cells").mkdir(exist_ok=True)
    shutil.copy(os.path.join(HERE, "peaks.json"), root)
    cfg = dict(TINY, name=config, driver="cg_assembled", chips=1, grid=[1, 1, 1])
    (root / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    cell = f"{config}.{traffic}"
    real = run.Bench().limits(f"nekbone_n7.{traffic}")
    (root / "cells" / f"{cell}.json").write_text(json.dumps({"limits": real}))
    spec = {
        "workloads": [{"name": cell, "config": config, "traffic": traffic, "chips": 1}],
        "end_to_end": [{"name": m, "unit": "u"} for m in metrics],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return run.Bench(str(root), str(root)), cell


def run_tiny(bench, cell, seed=12345678901):
    return run.run_cell(bench, cell, seed, 0.3, False, require_tpu=False,
                        log=lambda *a, **k: None)


def test_sound_tiny_run_is_correct(tmp_path):
    bench, cell = tiny_bench(tmp_path)
    res = run_tiny(bench, cell)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"fom_gflops", "setup_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {*run.CHECKS, "failed_solves"}
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_discovers_config_mix_driver_and_metric_added_as_files(tmp_path):
    bench, _ = tiny_bench(tmp_path)
    # a new driver: the reference solver itself, at the stated precision
    (tmp_path / "drivers" / "toy_driver.py").write_text(
        "import cg_assembled\n"
        "from reference import Reference, control_solve\n"
        "class Toy(cg_assembled.Driver):\n"
        "    def __init__(self, config, traffic, seed, devices):\n"
        "        super().__init__(config, traffic, seed, devices)\n"
        "        ref = Reference(self.degree, self.global_elems, self.lam)\n"
        "        self._solve = control_solve(ref, n_iter=traffic['n_iter'],\n"
        "                                    tol=traffic['tol'], precision='highest')\n"
        "    def block(self, out): out[0].block_until_ready()\n"
        "    def stats(self, out): return int(out[1]), 1, float(out[2]) ** 0.5\n"
        "    def answer(self, out):\n"
        "        import numpy as np; return np.asarray(out[0], np.float64)\n"
        "def build(*a): return Toy(*a)\n")
    (tmp_path / "metrics" / "toy_count.py").write_text(
        "def read(rec): return float(len(rec.stats))\n")
    (tmp_path / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"n_iter": 60, "tol": None, "precond": "none", "precond_kwargs": {},
         "ok_status": [1], "check_sample": 2}))
    cfg = dict(TINY, name="toy", driver="toy_driver", chips=1, grid=[1, 1, 1])
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(cfg))
    (tmp_path / "cells" / "toy.toy_mix.json").write_text(
        json.dumps({"limits": {"true_residual": 1e-5, "residual_gap": 1e-5}}))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "toy.toy_mix", "config": "toy",
                              "traffic": "toy_mix", "chips": 1})
    spec["end_to_end"].append({"name": "toy_count", "unit": "solves",
                               "workloads": ["toy.toy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    sys.path.insert(0, str(tmp_path / "drivers"))
    try:
        res = run_tiny(run.Bench(str(tmp_path), str(tmp_path)), "toy.toy_mix")
    finally:
        sys.path.remove(str(tmp_path / "drivers"))
    assert res["correct"] is True
    assert res["metrics"]["toy_count"]["value"] == res["attempted"]
    assert res["metrics"]["toy_count"]["unit"] == "solves"


def _fault_unchanged_state(monkeypatch):
    """Every step hands back the state it was given: x0 = 0 and r0.r0 = b.b."""
    import jax.numpy as jnp

    import repro.core as core

    orig = core.cg_assembled

    def unchanged(op, b, *a, **k):
        return orig(op, b, *a, **k)._replace(x=jnp.zeros_like(b), rdotr=jnp.vdot(b, b))

    monkeypatch.setattr(core, "cg_assembled", unchanged)


def _fault_not_converged(monkeypatch):
    """Solves that report they did not reach the tolerance count as failed."""
    import repro.core as core

    orig = core.cg_assembled

    def stalled(*a, **k):
        res = orig(*a, **k)
        return res._replace(status=res.status * 0 + int(core.SolveStatus.MAX_ITER))

    monkeypatch.setattr(core, "cg_assembled", stalled)


def _fault_half_the_elements(monkeypatch):
    import repro.core.operator as operator

    orig = operator.local_poisson

    def half(u, *a, **k):
        y = orig(u, *a, **k)
        return y.at[y.shape[0] // 2:].set(0.0)

    monkeypatch.setattr(operator, "local_poisson", half)


def _fault_altered_answer(monkeypatch):
    import repro.core as core

    orig = core.cg_assembled

    def altered(*a, **k):
        res = orig(*a, **k)
        return res._replace(x=res.x.at[0].add(1.0))

    monkeypatch.setattr(core, "cg_assembled", altered)


@pytest.mark.parametrize("fault, fails", [
    (_fault_unchanged_state, "true_residual"),
    (_fault_half_the_elements, "residual_gap"),
    (_fault_altered_answer, "residual_gap"),
])
@pytest.mark.parametrize("traffic", ["fixed100", "pmg_tol"])
def test_faults_in_the_timed_path_make_correct_false(tmp_path, monkeypatch, fault, fails,
                                                     traffic):
    fault(monkeypatch)
    bench, cell = tiny_bench(tmp_path, traffic=traffic, metrics=("setup_s",))
    res = run_tiny(bench, cell)
    assert res["correct"] is False
    assert not res["checks"][fails]["value"] <= res["checks"][fails]["limit"]  # NaN fails


def test_a_failed_solve_makes_correct_false(tmp_path, monkeypatch):
    _fault_not_converged(monkeypatch)
    bench, cell = tiny_bench(tmp_path, traffic="pmg_tol", metrics=("setup_s",))
    res = run_tiny(bench, cell)
    assert res["correct"] is False and res["failed"] == res["attempted"] >= 1
    assert res["checks"]["failed_solves"] == {"value": res["failed"], "limit": 0}


# ------------------------------------------------------------ the control
@pytest.mark.parametrize("cell, n_iter, tol", [
    ("nekbone_n7.fixed100", 100, None), ("nekbone_n7.pmg_tol", 500, 1e-6)])
def test_control_at_high_precision_fails_the_limit(cell, n_iter, tol):
    """The reference in the program's place, one precision step down, is
    not correct by the cell's own limits; at the configuration's own
    precision every number reads several times lower."""
    import jax
    import jax.numpy as jnp

    import calibrate
    import rhs
    from reference import Reference

    limits = run.Bench().limits(cell)
    ref = Reference(7, (4, 4, 4), 1.0)
    mk, kd = jax.jit(rhs.normal_fn(ref.n_global)), jnp.asarray(rhs.key_data(77))

    def checks(precision):
        samples = calibrate.control_samples(ref, mk, kd, n_iter=n_iter, tol=tol,
                                            precision=precision, solves=2)
        return run.judge(ref, samples, limits)

    high, highest = checks("high"), checks("highest")
    assert not run.passes(high)
    assert all(3 * highest[n]["value"] < high[n]["value"] for n in run.CHECKS)


# ------------------------------------------------------------ trace reduction
def _toy_trace():
    ms = 1_000_000
    ops = [("fusion.1", 0, 10 * ms), ("all-reduce.3", 5 * ms, 20 * ms),
           ("scatter.2", 30 * ms, 40 * ms), ("collective-permute-start.7", 60 * ms, 70 * ms)]
    host = [("window", 0, 100 * ms), ("wait", 0, 100 * ms), ("dispatch", 40 * ms, 60 * ms)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []},
                        "/device:TPU:1": {"ops": ops[:1], "modules": []}},
            "host": host}


def test_interval_arithmetic_of_the_reduction():
    s = tracefile.summarize(_toy_trace(), "window")
    assert s["window_s"] == pytest.approx(0.1)
    # device 0 busy 0-20, 30-40, 60-70 = 40 ms; device 1 busy 10 ms
    assert s["busy_s"] == pytest.approx((0.040 + 0.010) / 2)
    # collectives on device 0: 5-20 and 60-70; exposed 10-20 and 60-70
    assert s["collective_s"] == pytest.approx(0.025 / 2)
    assert s["collective_exposed_s"] == pytest.approx(0.020 / 2)
    # fusion.1 runs 10 ms on each device, all-reduce.3 15 ms on one: means
    assert s["top_ops"][:2] == [["fusion.1", pytest.approx(0.010)],
                                ["all-reduce.3", pytest.approx(0.0075)]]
    # gaps of device 0: 20-30, 40-60, 70-100; the one under "dispatch" named so
    assert [round(g[1], 6) for g in s["idle_gaps"]] == [0.03, 0.02, 0.01]
    assert s["idle_gaps"][1][0] == "dispatch"
    assert s["idle_gaps"][0][0] == "wait"


def test_collectives_are_told_by_opcode():
    assert tracefile.is_collective("all-reduce-start.12")
    assert tracefile.is_collective("collective-permute-done")
    assert not tracefile.is_collective("fusion.all-reduce")
    assert not tracefile.is_collective("scatter.4")


def test_reduction_of_a_recorded_chip_trace():
    """A trace recorded on one v5e chip: a few applies of a small operator."""
    trace = tracefile.load(os.path.join(TESTDATA, "small_operator"))
    assert list(trace["devices"]) == ["/device:TPU:0"]
    s = tracefile.summarize(trace, "window")
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["collective_s"] == 0 and s["collective_exposed_s"] == 0
    names = [n for n, _ in s["top_ops"]]
    assert names and all(t > 0 for _, t in s["top_ops"])
    assert any(tracefile.opcode(n) in ("scatter", "gather") or "fusion" in n for n in names)
    assert all(label for label, _ in s["idle_gaps"])
    per_call = tracefile.module_time_per_call(trace, 2, window="window")
    assert 0 < per_call <= s["window_s"]
