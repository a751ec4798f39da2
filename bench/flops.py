"""Operations and bytes of a GEMM, computed from its shape, and the
roofline's least time.  An architecture's own counts are its shape's
(``bench/arch/<kind>.py``).

Every count here is of the logical work the model requires, whatever
implements it: a GEMM's padding, a kernel's partial sums or a recomputed
block are not work.
"""

from __future__ import annotations

from typing import Tuple

BF16 = 2


def gemm_flops(m: int, n: int, k: int) -> float:
    return 2.0 * m * n * k


def gemm_bytes(m: int, n: int, k: int, itemsize: int = BF16,
               in_hbm: Tuple[bool, bool, bool] = (True, True, True)
               ) -> float:
    """HBM bytes of A (m, k) and B (k, n) read once and C (m, n) written
    once.  An array that ``in_hbm`` (for A, B, C) marks as held in on-chip
    memory moves no HBM bytes."""
    sizes = (m * k, k * n, m * n)
    return float(itemsize) * sum(s for s, h in zip(sizes, in_hbm) if h)


def least_time_s(flops: float, nbytes: float, peak) -> float:
    """The roofline's bound: the larger of compute time and memory time."""
    return max(flops / peak.flops, nbytes / peak.hbm_bytes_s)
