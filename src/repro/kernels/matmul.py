"""Parameterized Pallas TPU GEMM — the paper's §3.2 kernel, TPU-native.

Tuning parameters (see core/space.py for the PTX->Pallas mapping):
  bm, bn     output VMEM block (paper: M_L x N_L)
  bk         K-extent of the A/B slabs per grid step (paper: U)
  k_unroll   in-kernel unroll of the bk contraction (paper: K_S) — the MXU
             sees k_unroll independent (bm, bk/k_unroll) passes per step,
             giving the Mosaic scheduler ILP slack
  k_split    parallel split-K (paper: K_G).  TPUs have no global atomics, so
             the kernel materializes k_split partial outputs which the ops.py
             wrapper reduces — paying the paper's 'diminished write
             bandwidth' honestly
  order      grid-walk order: 0 = m-major (reuses B slabs across consecutive
             steps), 1 = n-major (reuses A slabs)
  acc32      accumulate in fp32 scratch (1) or the IO dtype (0)
  prefetch   conceptual DMA pipeline depth.  Pallas/Mosaic double-buffers
             sequential grid blocks automatically; the parameter is honored
             by the performance model and recorded for the generated config,
             but the kernel body is identical (documented DESIGN.md §3).

The kernel assumes shape-aligned operands; ``ops.matmul`` pads/slices (the
simulator charges that padding via its alignment-efficiency terms).
"""

from __future__ import annotations

import functools
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import COMPILER_PARAMS, interpret_mode


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                 k_unroll: int, acc32: bool):
    """One (bm, bn) output block: accumulate a_ref @ b_ref over the k grid."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    bk = a.shape[-1]
    acc_t = acc_ref.dtype
    # K_S: statically unrolled sub-tiles expose independent MXU passes.
    sub = bk // k_unroll
    acc = acc_ref[...]
    for u in range(k_unroll):
        a_u = jax.lax.slice_in_dim(a, u * sub, (u + 1) * sub, axis=1)
        b_u = jax.lax.slice_in_dim(b, u * sub, (u + 1) * sub, axis=0)
        # the MXU result is f32 (Mosaic refuses a narrower one); acc32=0
        # rounds it into the IO-dtype accumulator every step
        acc = acc + jnp.dot(a_u, b_u, preferred_element_type=jnp.float32
                            ).astype(acc_t)
    acc_ref[...] = acc

    @pl.when(k == k_steps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(a: jax.Array, b: jax.Array, cfg: Mapping[str, int], *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Aligned GEMM: a (M, K) @ b (K, N) -> (k_split, M, N) partials.

    Requires M % bm == 0, N % bn == 0, K % (k_split * bk) == 0 (ops.matmul
    guarantees this via padding).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    ks = cfg.get("k_split", 1)
    k_unroll = cfg.get("k_unroll", 1)
    acc32 = bool(cfg.get("acc32", 1))
    order = cfg.get("order", 0)
    assert M % bm == 0 and N % bn == 0 and K % (ks * bk) == 0, (
        (M, N, K), (bm, bn, bk, ks))
    gm, gn = M // bm, N // bn
    kps = K // (ks * bk)          # sequential k steps per split

    # grid = (split, outer, inner, k); `order` picks which of m/n is outer.
    if order == 0:
        grid = (ks, gm, gn, kps)
        a_map = lambda s, m, n, k: (m, s * kps + k)
        b_map = lambda s, m, n, k: (s * kps + k, n)
        o_map = lambda s, m, n, k: (s, m, n)
    else:
        grid = (ks, gn, gm, kps)
        a_map = lambda s, n, m, k: (m, s * kps + k)
        b_map = lambda s, n, m, k: (s * kps + k, n)
        o_map = lambda s, n, m, k: (s, m, n)

    acc_dtype = jnp.float32 if acc32 else a.dtype
    out_shape = jax.ShapeDtypeStruct((ks, M, N), a.dtype)

    kernel = functools.partial(
        _gemm_kernel, k_steps=kps, k_unroll=k_unroll, acc32=acc32)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), o_map),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret_mode(interpret),
    )(a, b)
