"""Where Pallas kernels run: natively (Mosaic) or through the interpreter.

Every ``*_pallas`` entry point resolves its ``interpret`` argument here, so
a caller that passes nothing gets the native kernel on a TPU and the
interpreter everywhere else.  The same resolution enforces the dtype rule
of the native path: Mosaic has no float64, so a float64 operand handed to
a native kernel is an error, never a silent downgrade.  ``pallas_call``
traces native kernels with 32-bit defaults, so they compile the same
whether or not the process runs with ``jax_enable_x64``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "default_interpret",
    "resolve_interpret",
    "native_dtype_ok",
    "pallas_call",
]


def default_interpret() -> bool:
    """Interpret Pallas kernels unless running on a real TPU."""
    return jax.default_backend() != "tpu"


def native_dtype_ok(dtype) -> bool:
    """Can a native (Mosaic) kernel take operands of ``dtype``?  Not float64."""
    return jnp.dtype(dtype) != jnp.float64


def resolve_interpret(interpret: bool | None, *dtypes, kernel: str) -> bool:
    """``interpret`` as given, else ``default_interpret()``; on the native
    path every operand dtype must pass ``native_dtype_ok``."""
    interp = default_interpret() if interpret is None else bool(interpret)
    if not interp:
        bad = [jnp.dtype(dt).name for dt in dtypes if not native_dtype_ok(dt)]
        if bad:
            raise TypeError(
                f"{kernel}: native Pallas kernels have no {bad[0]} support "
                "(Mosaic lowers no float64); keep float64 solves on the XLA "
                "path or cast the operands to float32"
            )
    return interp


def pallas_call(kernel, *, interpret: bool, **kwargs):
    """``pl.pallas_call`` whose native form is traced with x64 off.

    Under ``jax_enable_x64`` the Python ints of a grid index map trace as
    int64, which Mosaic cannot lower ("failed to legalize operation
    'func.return'").  Native kernels take no float64, so they trace with
    32-bit defaults; the interpreter keeps the caller's setting, because
    float64 kernels run there.
    """
    call = pl.pallas_call(kernel, interpret=interpret, **kwargs)
    if interpret:
        return call

    def native(*operands):
        with jax.enable_x64(False):
            return call(*operands)

    return native
