"""Run one cell of the on-chip benchmark once and print its result line.

    python3 benchmarks/tpu/run.py --workload nekbone_n7.fixed100 \
        --seed 1234 --seconds 20 --trace 0

A cell is ``<config>.<traffic>`` in ``BENCHMARK.json``.  Everything that
belongs to one configuration, traffic mix, driver or metric lives in a
file of its own under this directory and is found by name:

    configs/<config>.json     the deployment (names its driver)
    traffic/<traffic>.json    the kind of solve requested
    drivers/<driver>.py       builds the solve program: ``build(...)``
    metrics/<metric>.py       one reader per metric: ``read(rec)``; a name
                              ``base.suffix`` falls back to ``base.py``
    cells/<cell>.json         the limits of the correctness comparison

The run: set-up (problem build, preconditioner, compile or cache load, one
warm-up solve) is ``setup_s``.  Then whole solves run back to back, one at
a time, until ``--seconds`` have passed; the window ends when the last
solve that started before that mark completes.  After the window a sample
of the solutions, drawn from the seed, is judged against the plain float64
reference (``reference.py``).  ``--trace 1`` profiles the window and the
standalone probes the cell's metrics ask for, and reports the per-layer
metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # setup_s counts from here

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
PROBE_CALLS = 20  # standalone applies per probe in a traced run


class Refused(RuntimeError):
    """The run cannot measure this cell here: no result is printed."""


# ------------------------------------------------------------ discovery
def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """BENCHMARK.json and the files it names, found by name under ``here``."""

    def __init__(self, checkout: str = CHECKOUT, here: str = HERE):
        self.here = here
        self.spec = _load_json(os.path.join(checkout, "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise Refused(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return _load_json(os.path.join(self.here, "configs", f"{name}.json"))

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.here, "traffic", f"{name}.json"))

    def limits(self, cell: str) -> dict:
        return _load_json(os.path.join(self.here, "cells", f"{cell}.json"))["limits"]

    def driver(self, name: str):
        return _load_module(os.path.join(self.here, "drivers", f"{name}.py"),
                            f"bench_driver_{name}")

    def metric(self, name: str):
        """The reader of ``name``; ``base.suffix`` falls back to ``base.py``."""
        for stem in (name, name.split(".")[0]):
            path = os.path.join(self.here, "metrics", f"{stem}.py")
            if os.path.exists(path):
                return _load_module(path, "bench_metric_" + stem.replace(".", "_"))
        raise Refused(f"no reader metrics/{name}.py for metric {name!r}")

    def metrics_of(self, cell: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def peaks(self, kind: str) -> dict:
        table = _load_json(os.path.join(self.here, "peaks.json"))
        if kind not in table:
            raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
        return table[kind]


class CompileCounter:
    """Counts lowerings and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax._src import monitoring

        self.active, self.count = False, 0
        self._monitoring = monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event in self.EVENTS:
            self.count += 1

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._on)


def _start_trace(path: str):
    """Start the profiler without Python function tracing (small traces)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)


def _platform_check(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX sees {devices[0].platform} devices only")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    return devices


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True, log=print) -> dict:
    """One run of one cell; returns the result object (last stdout line)."""
    import jax

    w = bench.workload(workload)
    config, traffic = bench.config(w["config"]), bench.traffic(w["traffic"])
    limits = bench.limits(workload)
    e2e = bench.metrics_of(workload, trace=False)
    layer = bench.metrics_of(workload, trace=True)
    readers = {m["name"]: bench.metric(m["name"]) for m in (layer if trace else e2e)}
    if require_tpu:
        devices = _platform_check(w["chips"])
    else:
        devices = jax.devices()
    devices = devices[: w["chips"]]
    peaks = bench.peaks(devices[0].device_kind) if require_tpu else None

    # ---- set-up: build, compile or load, warm up every shape the window uses
    driver = bench.driver(config["driver"]).build(config, traffic, seed, devices)
    probes = {}
    if trace:
        for name, mod in readers.items():
            target = getattr(mod, "PROBE", None)
            fn = getattr(driver, target, None) if target else None
            if fn is not None and target not in probes:
                probes[target] = jax.jit(fn)
        for fn in probes.values():
            jax.block_until_ready(fn(driver.probe_input))
    driver.block(driver.solve(0))
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s:.3f}", flush=True)

    # ---- the window: closed loop, one solve at a time
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        counter = CompileCounter()
        outs = []
        counter.active = True
        if trace:
            _start_trace(tdir)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            while time.perf_counter() - t0 < seconds:
                with jax.profiler.TraceAnnotation("dispatch"):
                    out = driver.solve(len(outs))
                with jax.profiler.TraceAnnotation("wait"):
                    driver.block(out)
                outs.append(out)
        window_s = time.perf_counter() - t0
        counter.active = False
        counter.close()
        if trace:
            jax.profiler.stop_trace()
        log(f"compiles_in_window {counter.count}", flush=True)

        # ---- after the window: memory, counts, the trace, standalone probes
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        stats = [driver.stats(o) for o in outs]      # (iterations, status, ||r||)
        ok = set(traffic["ok_status"])
        failed = sum(s[1] not in ok for s in stats)
        trace_summary, probe_s = None, {}
        if trace:
            import tracefile

            trace_summary = tracefile.summarize(tracefile.load(tdir), "window")
            for target, fn in probes.items():
                pdir = os.path.join(tdir, "probe_" + target)
                _start_trace(pdir)
                with jax.profiler.TraceAnnotation("probe"):
                    for _ in range(PROBE_CALLS):
                        y = fn(driver.probe_input)
                    jax.block_until_ready(y)
                jax.profiler.stop_trace()
                probe_s[target] = tracefile.module_time_per_call(
                    tracefile.load(pdir), PROBE_CALLS)

    # ---- the check: a sample of the window's solutions against the reference
    checks = check_solutions(driver, outs, stats, seed, traffic, limits)
    checks["failed_solves"] = {"value": failed, "limit": 0}
    correct = bool(outs) and passes(checks)

    # what the window, the check and the trace leave for the metric readers
    rec = types.SimpleNamespace(
        config=config, traffic=traffic, setup_s=setup_s, window_s=window_s,
        stats=stats, ok_status=ok, peaks=peaks, trace=trace_summary, probe_s=probe_s)
    metrics = {}
    for m in (layer if trace else e2e):
        value = readers[m["name"]].read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(outs), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {"device_ops": trace_summary["top_ops"],
                               "idle_gaps": trace_summary["idle_gaps"]}
    result["checks"] = checks
    return result


CHECKS = ("true_residual", "residual_gap")  # the numbers Reference.judge gives


def judge(ref, samples, limits) -> dict:
    """Each number compared, the largest over ``samples``, beside its limit.

    ``samples`` yields ``(b, x, reported ||r||)`` of solves, one at a
    time so that few are held at once; none at all reads infinite.
    """
    values = {name: [] for name in CHECKS}
    for b, x, rnorm in samples:
        for name, value in ref.judge(b, x, rnorm).items():
            values[name].append(value)
    # np.max keeps a NaN
    return {name: {"value": float(np.max(v)) if v else float("inf"), "limit": limits[name]}
            for name, v in values.items()}


def passes(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())


def check_solutions(driver, outs, stats, seed, traffic, limits) -> dict:
    """Judge a sample of the window's solutions against the reference.

    For each sampled solve the float64 reference gives the true residual
    ||b - A x|| / ||b|| of the solution the program returned, and its gap
    to the residual the solver reports.  The sample is drawn from the
    seed, and the solve that took the most iterations is always in it.
    """
    from reference import Reference

    pick = set()
    if outs:
        rng = np.random.default_rng(seed)
        n = len(outs)
        pick = set(rng.choice(n, size=min(traffic["check_sample"], n), replace=False).tolist())
        pick.add(int(np.argmax([s[0] for s in stats])))
    ref = Reference(driver.degree, driver.global_elems, driver.lam)
    samples = ((driver.rhs(i), driver.answer(outs[i]), stats[i][2]) for i in sorted(pick))
    return judge(ref, samples, limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
        import jax

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        result = run_cell(Bench(), args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
