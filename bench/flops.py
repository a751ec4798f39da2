"""Operations and bytes of the served work, computed from shapes.

Every count here is of the logical work the model requires, whatever
implements it: a GEMM's padding, a kernel's partial sums or a recomputed
block are not work.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

BF16 = 2


def gemm_flops(m: int, n: int, k: int) -> float:
    return 2.0 * m * n * k


def gemm_bytes(m: int, n: int, k: int, itemsize: int = BF16) -> float:
    """A (m, k) and B (k, n) read once, C (m, n) written once."""
    return float(itemsize) * (m * k + k * n + m * n)


def least_time_s(flops: float, nbytes: float, peak) -> float:
    """The roofline's bound: the larger of compute time and memory time."""
    return max(flops / peak.flops, nbytes / peak.hbm_bytes_s)


@dataclasses.dataclass(frozen=True)
class DenseShape:
    """The sizes of a dense GQA decoder that the counts need."""

    layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int

    def projections(self) -> List[Tuple[str, int, int]]:
        """(name, N, K) of each projection GEMM of one layer: x (M, K) @ W."""
        d, q, kv, f = (self.d_model, self.n_heads * self.head_dim,
                       self.n_kv * self.head_dim, self.d_ff)
        return [("q", q, d), ("k", kv, d), ("v", kv, d), ("o", d, q),
                ("gate", f, d), ("up", f, d), ("down", d, f)]

    def projection_params(self) -> int:
        """Projection weights of one layer."""
        return sum(n * k for _, n, k in self.projections())


def attention_flops(shape: DenseShape, context: int) -> float:
    """Scores and weighted sum of one token over ``context`` positions, all
    layers: 2 * (QK^T + PV) per head."""
    return 4.0 * shape.layers * shape.n_heads * shape.head_dim * context


def request_model_flops(shape: DenseShape, prompt_len: int,
                        decoded: int) -> float:
    """Model FLOPs that one request's live tokens require: the prompt's
    tokens in prefill and ``decoded`` tokens fed back through decode, each
    through every projection and attending over its real context, and the
    head for each position whose logits are sampled (the prompt's last and
    every decoded one)."""
    per_token = 2.0 * shape.layers * shape.projection_params()
    tokens = prompt_len + decoded
    # position p attends over p + 1 positions: sum over p < tokens
    context_sum = tokens * (tokens + 1) // 2
    head = 2.0 * shape.d_model * shape.vocab * (1 + decoded)
    return (per_token * tokens + attention_flops(shape, 1) * context_sum
            + head)
