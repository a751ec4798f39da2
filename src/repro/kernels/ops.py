"""Jit-ready wrappers around the Pallas kernels: padding, partial-sum
reduction, and config defaulting.  These are the public kernel entry points;
models call them through ``dispatch`` which injects tuned configurations.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.space import conv_blocks, conv_input, gemm_default_config

from . import attention as _attention
from . import conv as _conv
from . import matmul as _matmul
from . import ssd as _ssd
from .common import interpret_mode

DEFAULT_CONV = {"b_npq": 128, "b_k": 128, "b_c": 128, "rs_unroll": 1,
                "c_split": 1, "order": 0, "acc32": 1, "prefetch": 2}
DEFAULT_ATTN = {"b_q": 128, "b_kv": 128, "acc32": 1, "prefetch": 2}
DEFAULT_SSD = {"chunk": 128, "b_heads": 1, "acc32": 1, "prefetch": 2}


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fit_gemm(cfg: Mapping[str, int], M: int, N: int, K: int
              ) -> Dict[str, int]:
    """Shrink blocks that exceed the problem — keeps any legal-ish config
    runnable so the tuner can probe freely."""
    bm, bn, bk, ks = cfg["bm"], cfg["bn"], cfg["bk"], cfg["k_split"]
    while bm > M and bm > 8:
        bm //= 2
    while bn > N and bn > 128:
        bn //= 2
    while bk * ks > K and bk > 128:
        bk //= 2
    while ks > 1 and bk * ks > max(K, bk):
        ks //= 2
    ku = cfg["k_unroll"]
    while ku > 1 and bk % (ku * 128):
        ku //= 2
    return {**cfg, "bm": bm, "bn": bn, "bk": bk, "k_split": ks, "k_unroll": ku}


def matmul(a: jax.Array, b: jax.Array,
           cfg: Optional[Mapping[str, int]] = None, *,
           interpret: Optional[bool] = None) -> jax.Array:
    """C = A @ B through the parameterized Pallas kernel (pads + reduces).

    Without ``cfg`` the blocks follow the shape (``gemm_default_config``);
    a given config fills its missing fields from there and shrinks to fit."""
    M, K = a.shape
    _, N = b.shape
    base = gemm_default_config(M, N, K, a.dtype.itemsize * 8)
    cfg = base if cfg is None else _fit_gemm({**base, **cfg}, M, N, K)
    bm, bn, bk, ks = cfg["bm"], cfg["bn"], cfg["bk"], cfg["k_split"]
    a_p = _pad_to(_pad_to(a, 0, bm), 1, bk * ks)
    b_p = _pad_to(_pad_to(b, 0, bk * ks), 1, bn)
    parts = _matmul.matmul_pallas(a_p, b_p, cfg, interpret=interpret)
    out = parts.sum(axis=0) if ks > 1 else parts[0]
    return out[:M, :N]


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   tiles: Tuple[int, int, int], *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Rows of ``lhs`` (M, K), sorted by group, each times its group's
    ``rhs[g]`` (K, N), through megablox's Pallas grouped GEMM with
    ``tiles`` (tm, tk, tn).  M pads to the row tile; the padded rows belong
    to no group and are cut off."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    M = lhs.shape[0]
    out = gmm(_pad_to(lhs, 0, tiles[0]), rhs, group_sizes.astype(jnp.int32),
              lhs.dtype, tuple(tiles), interpret=interpret_mode(interpret))
    return out[:M]


def conv2d(i: jax.Array, f: jax.Array,
           cfg: Optional[Mapping[str, int]] = None, *,
           interpret: Optional[bool] = None) -> jax.Array:
    """SAME/stride-1 conv i (N,H,W,C) * f (R,S,C,K) -> (N,H,W,K)."""
    cfg = {**DEFAULT_CONV, **(cfg or {})}
    N, H, W, C = i.shape
    R, S, _, K = f.shape
    # the output width is padded to the sublane tile (the extra columns see
    # zero input and are sliced off), so a window flattens without relayout
    blk = conv_blocks(cfg, conv_input(N, H, W, C, K, R, S))
    P, Q, b_p = H, blk["Q"], blk["b_p"]
    b_k, b_c, cs = blk["b_k"], blk["b_c"], blk["c_split"]
    cfg = {**cfg, "b_k": b_k, "b_c": b_c, "c_split": cs}

    # SAME padding (odd filters center; even filters follow XLA's convention)
    pt = (R - 1) // 2
    pb = R - 1 - pt
    pl_ = (S - 1) // 2
    pr = S - 1 - pl_ + Q - W
    i_pad = jnp.pad(i, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    i_pad = _pad_to(i_pad, 3, b_c * cs)
    f_p = _pad_to(_pad_to(f, 2, b_c * cs), 3, b_k)

    parts = _conv.conv2d_pallas(i_pad, f_p, cfg, P=P, Q=Q, b_p=b_p,
                                interpret=interpret)
    out = parts.sum(axis=0) if cs > 1 else parts[0]
    return out[:, :, :W, :K]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    cfg: Optional[Mapping[str, int]] = None, *,
                    causal: bool = True, q_offset: int = 0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Padded flash attention; masks padded KV via the causal machinery."""
    cfg = {**DEFAULT_ATTN, **(cfg or {})}
    B, Hq, Lq, D = q.shape
    Lkv = k.shape[2]
    b_q = min(cfg["b_q"], max(Lq, 1))
    b_kv = min(cfg["b_kv"], max(Lkv, 1))
    q_p = _pad_to(q, 2, b_q)
    k_p = _pad_to(k, 2, b_kv)
    v_p = _pad_to(v, 2, b_kv)
    Lq_p, Lkv_p = q_p.shape[2], k_p.shape[2]
    eff_offset = q_offset if causal else 0
    if not causal and Lkv_p != Lkv:
        # non-causal with padded KV: mask pads by position (offset trick)
        causal, eff_offset = True, Lkv - 1 - (Lq - 1)
    out = _attention.flash_attention_pallas(
        q_p, k_p, v_p, {**cfg, "b_q": b_q, "b_kv": b_kv}, causal=causal,
        q_offset=eff_offset, interpret=interpret)
    return out[:, :, :Lq]


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
             cm: jax.Array, cfg: Optional[Mapping[str, int]] = None, *,
             interpret: Optional[bool] = None) -> jax.Array:
    """Padded SSD chunk scan (pads L; padded steps have dt=0 => identity)."""
    cfg = {**DEFAULT_SSD, **(cfg or {})}
    B, L, H, P = x.shape
    chunk = min(cfg["chunk"], L)
    bh = cfg.get("b_heads", 1)
    while H % bh:
        bh //= 2
    x_p = _pad_to(x, 1, chunk).transpose(0, 2, 1, 3)          # (B, H, L, P)
    dt_p = _pad_to(dt, 1, chunk).transpose(0, 2, 1)[..., None]  # (B, H, L, 1)
    bm_p = _pad_to(bm, 1, chunk)
    cm_p = _pad_to(cm, 1, chunk)
    out = _ssd.ssd_scan_pallas(x_p, dt_p, a.astype(jnp.float32), bm_p, cm_p,
                               {**cfg, "chunk": chunk, "b_heads": bh},
                               interpret=interpret)
    return out.transpose(0, 2, 1, 3)[:, :L]
