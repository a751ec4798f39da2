"""What every Pallas call in this package shares: its VMEM budget and the
choice between compiling the kernel and interpreting it."""

from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas import tpu as pltpu

from repro.core.space import VMEM_LIMIT_BYTES

# the same budget core/space.py bounds every legal config by, so a config the
# tuner may pick is one the compiler accepts
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` as given; when None, compiled on a TPU and interpreted
    on any other backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
