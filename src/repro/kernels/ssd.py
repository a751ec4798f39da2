"""Mamba-2 SSD (state-space duality) chunk scan — tunable Pallas kernel.

Beyond-paper op: the paper's tuner is extended to the SSD chunked scan
(DESIGN.md §5, mamba2/jamba architectures).  The chunked algorithm
(arXiv:2405.21060) splits the sequence into chunks of length `chunk`:
within a chunk the recurrence is a masked quadratic form (MXU-friendly),
across chunks a (P x S) state is carried — here in VMEM scratch across
sequential grid steps, the TPU-idiomatic substitute for the paper's GPU
inter-block communication.

Tunables (core/space.py SSD_SPACE): chunk, b_heads, acc32, prefetch.

Layouts (head-major, so every block's last two dims are (chunk, P),
(chunk, 1) or (chunk, S) and tile onto the TPU's (8, 128) grid):
x (B, H, L, P), dt (B, H, L, 1), A (H,) in SMEM, Bm/Cm (B, L, S)
[ngroups=1], y (B, H, L, P).  ops.ssd_scan transposes from the model's
(B, L, H, P) layout and pads L to a chunk multiple.
"""

from __future__ import annotations

import functools
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import COMPILER_PARAMS, interpret_mode

_HI = jax.lax.Precision.HIGHEST     # decay exponents must not round to bf16
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref, *,
                b_heads: int):
    hb = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    f32 = jnp.float32
    bm = b_ref[0].astype(f32)                # (chunk, S)
    cm = c_ref[0].astype(f32)                # (chunk, S)
    chunk = bm.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = jj <= ii
    # C_i . B_j, shared by every head (ngroups=1)
    cb = jax.lax.dot_general(cm, bm, _NT, precision=_HI,
                             preferred_element_type=f32)
    for h in range(b_heads):
        a = a_ref[hb * b_heads + h]
        x = x_ref[0, h].astype(f32)          # (chunk, P)
        dt = dt_ref[0, h].astype(f32)        # (chunk, 1)
        adt = dt * a                         # per-step log-decay
        # seg[i, j] = sum_{j < k <= i} adt[k]: one matmul against the
        # row-scaled strictly-lower mask (no cumsum primitive needed)
        seg = jnp.dot(causal.astype(f32), jnp.where(jj < ii, adt, 0.0),
                      precision=_HI, preferred_element_type=f32)
        cum = seg[:, :1] + adt[:1, :]        # (chunk, 1) inclusive cumsum

        # -- intra-chunk: masked quadratic form (the 'duality' matmul) -----
        scores = jnp.where(causal, cb * jnp.exp(seg), 0.0)
        y = jnp.dot(scores, x * dt, precision=_HI,
                    preferred_element_type=f32)
        # -- inter-chunk: contribution of the carried state ----------------
        state = state_ref[h]                 # (P, S)
        y = y + jnp.exp(cum) * jax.lax.dot_general(
            cm, state, _NT, precision=_HI, preferred_element_type=f32)
        y_ref[0, h] = y.astype(y_ref.dtype)

        # -- state update ---------------------------------------------------
        # Mosaic broadcasts one axis at a time, so every decay stays a row
        # or a column: seg's last row is tot - cum[j], the tail decay of j
        tail = jnp.where(ii == jj, jnp.exp(seg[chunk - 1:, :]), 0.0)
        b_tail = jnp.dot(tail, bm, precision=_HI, preferred_element_type=f32)
        contrib = jax.lax.dot_general(x * dt, b_tail, _TN, precision=_HI,
                                      preferred_element_type=f32)
        tot = jnp.sum(jnp.broadcast_to(adt, bm.shape), axis=0, keepdims=True)
        state_ref[h] = state * jnp.exp(tot) + contrib       # tot: (1, S)


def ssd_scan_pallas(x: jax.Array, dt: jax.Array, a: jax.Array,
                    bm: jax.Array, cm: jax.Array, cfg: Mapping[str, int], *,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Aligned SSD scan on head-major operands: x (B, H, L, P),
    dt (B, H, L, 1), a (H,) f32, bm/cm (B, L, S) -> y (B, H, L, P).
    L % chunk == 0 and H % b_heads == 0 required."""
    B, H, L, P = x.shape
    S = bm.shape[-1]
    chunk = min(cfg["chunk"], L)
    bh = min(cfg.get("b_heads", 1), H)
    assert L % chunk == 0 and H % bh == 0, ((L, H), (chunk, bh))
    grid = (B, H // bh, L // chunk)          # chunks innermost: sequential

    x_map = lambda b, h, c: (b, h, c, 0)
    bc_map = lambda b, h, c: (b, c, 0)
    kernel = functools.partial(_ssd_kernel, b_heads=bh)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bh, chunk, P), x_map),
            pl.BlockSpec((1, bh, chunk, 1), x_map),
            pl.BlockSpec((1, chunk, S), bc_map),
            pl.BlockSpec((1, chunk, S), bc_map),
        ],
        out_specs=pl.BlockSpec((1, bh, chunk, P), x_map),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((bh, P, S), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret_mode(interpret),
    )(a, x, dt, bm, cm)
