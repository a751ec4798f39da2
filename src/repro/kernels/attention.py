"""Flash attention with tunable blocks — beyond-paper op (paper §9 asks for a
front-end 'beyond GEMM and CONV'; attention is the modern bottleneck).

Online-softmax streaming over KV blocks; GQA handled by head-index mapping
(no KV replication in HBM).  Tunables (core/space.py ATTENTION_SPACE):
  b_q    query rows per block
  b_kv   KV rows streamed per grid step
  acc32  accumulator precision
  prefetch  perf-model pipeline depth (Pallas double-buffers automatically)

Layouts: q (B, Hq, Lq, D), k/v (B, Hkv, Lkv, D), out (B, Hq, Lq, D).
ops.flash_attention pads Lq/Lkv and handles the causal offset for decode
(Lq tokens attending to a Lkv >= Lq cache).
"""

from __future__ import annotations

import functools
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import COMPILER_PARAMS, interpret_mode

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 kv_steps: int, b_q: int, b_kv: int, causal: bool,
                 q_offset: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                      # (b_q, D)
    k = k_ref[0, 0]                      # (b_kv, D)
    v = v_ref[0, 0]                      # (b_kv, D)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        # global positions: query row iq*b_q + i (+ cache offset for decode),
        # key column ik*b_kv + j
        rows = q_offset + iq * b_q + jax.lax.broadcasted_iota(
            jnp.int32, (b_q, b_kv), 0)
        cols = ik * b_kv + jax.lax.broadcasted_iota(
            jnp.int32, (b_q, b_kv), 1)
        s = jnp.where(cols <= rows, s, NEG_INF)

    m_prev = m_ref[...]                  # (b_q, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jnp.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ik == kv_steps - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           cfg: Mapping[str, int], *, causal: bool = True,
                           q_offset: int = 0,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Aligned flash attention.  Lq % b_q == 0, Lkv % b_kv == 0 required."""
    B, Hq, Lq, D = q.shape
    _, Hkv, Lkv, _ = k.shape
    b_q = min(cfg["b_q"], Lq)
    b_kv = min(cfg["b_kv"], Lkv)
    assert Lq % b_q == 0 and Lkv % b_kv == 0, ((Lq, Lkv), (b_q, b_kv))
    assert Hq % Hkv == 0
    group = Hq // Hkv
    gq, gkv = Lq // b_q, Lkv // b_kv
    scale = 1.0 / (D ** 0.5)

    grid = (B, Hq, gq, gkv)

    q_map = lambda b, h, iq, ik: (b, h, iq, 0)
    kv_map = lambda b, h, iq, ik: (b, h // group, ik, 0)
    o_map = lambda b, h, iq, ik: (b, h, iq, 0)

    kernel = functools.partial(
        _attn_kernel, kv_steps=gkv, b_q=b_q, b_kv=b_kv, causal=causal,
        q_offset=q_offset, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, b_q, D), q_map),
            pl.BlockSpec((1, 1, b_kv, D), kv_map),
            pl.BlockSpec((1, 1, b_kv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, b_q, D), o_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((b_q, 1), jnp.float32),      # running max
            pltpu.VMEM((b_q, 1), jnp.float32),      # running denominator
            pltpu.VMEM((b_q, D), jnp.float32),      # output accumulator
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret_mode(interpret),
    )(q, k, v)
