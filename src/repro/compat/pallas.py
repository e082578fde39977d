"""Pallas-TPU launch settings: compiler params, VMEM scratch, interpret mode.

Kernels call :func:`tpu_compiler_params` / :func:`vmem` /
:func:`interpret_mode` and never touch ``pltpu`` attributes directly, so
a JAX upgrade that renames them is a change to this module only.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.experimental.pallas import tpu as pltpu


def tpu_compiler_params(*, dimension_semantics: Sequence[str]):
    """TPU compiler params for a kernel grid."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))


def vmem(shape: Sequence[int], dtype) -> Any:
    """VMEM scratch allocation."""
    return pltpu.VMEM(tuple(shape), dtype)


def interpret_mode() -> bool:
    """Whether Pallas kernels run in ``interpret=True`` mode.

    False on a TPU backend (compiled Mosaic kernels); True on the CPU
    backend, where interpret mode executes the kernel body with the same
    semantics at Python speed, which keeps the test suite runnable
    without a chip.  Any other backend is an error: a kernel must never
    fall back to interpret mode beside a device it should run on.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on a TPU, or in interpret mode "
                       f"on the CPU; the default backend is {backend!r}")
