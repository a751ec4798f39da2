"""Shifted-window implicit-GEMM convolution — the paper's §3.3 on TPU.

The paper scrambles I/F tiles into shared memory through an indirection
table so the inner loop is free of integer arithmetic.  TPUs want static
layouts instead (DESIGN.md §3): we keep the padded input slab resident in
VMEM and walk the (r, s) filter offsets as *statically shifted slices*, each
feeding one MXU matmul of the implicit-GEMM view
    (N*P*Q, C*R*S) x (C*R*S, K).

Tuning parameters (core/space.py):
  b_npq      output spatial block, realized as b_p = max(b_npq // Q, 1)
             full-width row bands (windows must stay contiguous)
  b_k        output-channel block
  b_c        input-channel slab per grid step
  c_split    parallel split of the C reduction (paper: C_G) — materialized
             partials, reduced by ops.conv2d
  rs_unroll  scheduling granularity of the fully-unrolled (r, s) walk; the
             kernel body unrolls completely (R, S are static), the parameter
             informs the performance model
  order/acc32/prefetch  as in matmul.py

Layouts: I (N, H, W, C), F (R, S, C, K), O (N, P, Q, K); SAME padding,
stride 1 (the DeepBench regime the paper evaluates).  ops.conv2d pads
spatially+channel-wise and slices the result; it also pads the output width
Q to the sublane tile, so each (b_p, Q, b_c) window flattens to a matmul
operand without a relayout.
"""

from __future__ import annotations

import functools
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import COMPILER_PARAMS, interpret_mode


def _conv_kernel(i_ref, f_ref, o_ref, acc_ref, *, c_steps: int, b_p: int,
                 Q: int, R: int, S: int):
    """One (b_p x Q, b_k) output block, accumulated over the C grid axis."""
    p = pl.program_id(2)
    c = pl.program_id(4)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b_c = i_ref.shape[-1]               # i_ref: (1, Hp, Wp, b_c) slab in VMEM
    acc = acc_ref[...]                  # (b_p * Q, b_k)
    row0 = p * b_p
    for r in range(R):                  # fully-unrolled shifted-window walk
        for s in range(S):
            win = i_ref[0, pl.ds(row0 + r, b_p), pl.ds(s, Q), :]
            lhs = win.reshape(b_p * Q, b_c)              # Q is tile-aligned
            rhs = f_ref[r, s]                            # (b_c, b_k)
            acc = acc + jnp.dot(lhs, rhs, preferred_element_type=jnp.float32
                                ).astype(acc.dtype)     # as in matmul.py
    acc_ref[...] = acc

    @pl.when(c == c_steps - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       .reshape(b_p, Q, acc_ref.shape[-1])
                       .astype(o_ref.dtype))


def conv2d_pallas(i_pad: jax.Array, f: jax.Array, cfg: Mapping[str, int], *,
                  P: int, Q: int, b_p: int,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Aligned conv on pre-padded input.

    i_pad: (N, P + R - 1, Q + S - 1, C) — spatially SAME-padded, P % b_p == 0,
           C % (c_split * b_c) == 0, channels padded.
    f:     (R, S, C, K), K % b_k == 0.
    b_p:   output rows per block (ops.conv2d derives it from b_npq).
    Returns (c_split, N, P, Q, K) partial outputs.
    """
    N, Hp, Wp, C = i_pad.shape
    R, S, C2, K = f.shape
    assert C == C2 and Hp == P + R - 1 and Wp == Q + S - 1
    b_k, b_c = cfg["b_k"], cfg["b_c"]
    cs = cfg.get("c_split", 1)
    acc32 = bool(cfg.get("acc32", 1))
    assert P % b_p == 0, (P, b_p)
    assert K % b_k == 0 and C % (cs * b_c) == 0, ((K, C), (b_k, b_c, cs))
    gp, gk = P // b_p, K // b_k
    cps = C // (cs * b_c)              # sequential C steps per split

    grid = (cs, N, gp, gk, cps)

    i_map = lambda s_, n, p, k, c: (n, 0, 0, s_ * cps + c)
    f_map = lambda s_, n, p, k, c: (0, 0, s_ * cps + c, k)
    o_map = lambda s_, n, p, k, c: (s_, n, p, 0, k)

    acc_dtype = jnp.float32 if acc32 else i_pad.dtype
    kernel = functools.partial(_conv_kernel, c_steps=cps, b_p=b_p, Q=Q,
                               R=R, S=S)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, b_c), i_map),
            pl.BlockSpec((R, S, b_c, b_k), f_map),
        ],
        out_specs=pl.BlockSpec((1, 1, b_p, Q, b_k), o_map),
        out_shape=jax.ShapeDtypeStruct((cs, N, P, Q, K), i_pad.dtype),
        scratch_shapes=[pltpu.VMEM((b_p * Q, b_k), acc_dtype)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret_mode(interpret),
    )(i_pad, f)
