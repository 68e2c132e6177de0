"""Child processes for the sections that time virtual CPU devices.

``fig456``, ``table2`` and ``exchange`` run each configuration in a fresh
interpreter that pins ``--xla_force_host_platform_device_count``.  A chip
belongs to one process at a time, and the parent has already touched JAX,
so on a TPU backend such a child would fail or hang: ``run_child`` refuses
there before spawning anything.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


def run_child(
    code: str, extra_env: dict | None = None, timeout: int = 900, *, section: str
):
    """Run ``code`` in a fresh interpreter; return its last stdout line as json."""
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"benchmark section {section!r} spawns child processes that time "
            "virtual CPU devices; this process already holds the TPU, so a "
            "child cannot reach it. Run the section with JAX_PLATFORMS=cpu."
        )
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=timeout,
    )
    if res.returncode != 0:
        raise RuntimeError(res.stderr[-2000:])
    return json.loads(res.stdout.strip().splitlines()[-1])
