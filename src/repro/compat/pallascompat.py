"""Pallas-TPU call settings: compiler params and interpret mode.

Kernels build their compiler params through :func:`tpu_compiler_params`
(the compat boundary keeps ``CompilerParams`` in this package), and decide
whether to run the Pallas interpreter through :func:`resolve_interpret` —
the one place that reads the backend, so no kernel, dispatch wrapper or
autotune sweep can time the interpreter on a TPU.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas import tpu as pltpu


def tpu_compiler_params(**kwargs):
    """Build the TPU compiler-params object."""
    return pltpu.CompilerParams(**kwargs)


def on_tpu() -> bool:
    """True when the default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for a Pallas call: ``None`` follows the backend (the
    interpreter everywhere but on a TPU).  An explicit ``False`` compiles
    for the chip even from a CPU process, which is how a described TPU
    topology is compiled for; an explicit ``True`` on a TPU is refused."""
    if interpret is None:
        return not on_tpu()
    if interpret and on_tpu():
        raise ValueError("Pallas interpret mode requested on a TPU backend; "
                         "leave interpret unset to compile the kernel")
    return bool(interpret)
